"""Path: the fused SPMD S²FL round step of a decoder LM
(``core.round_step.make_s2fl_train_step``), compiled once with the
shardings of ``train_step_shardings`` on a (chips, 1) data x model mesh
from ``launch.mesh.make_mesh``, driven step after step.

Set-up makes the weights from the seed in one jitted call (the layout and
values of ``reference/<family>.py``), makes a small pool of distinct
batches on the device, compiles the step ahead of time, and takes the
first three steps through the window's own call on three different
batches, keeping the losses and the leaf norms of ``w1 - w0`` and
``w3 - w0`` (``w0`` made again inside a jitted call, so no copy of it is
kept). The window goes on from step four, cycling through the pool, with
``AHEAD_S`` of steps queued beyond the one it waits for. The check frees
the program's state and runs the reference over the same three steps at
``highest`` precision.

Traffic keys: ``seq_len``, ``sequences`` per step (one client each),
``groups`` (balance groups), ``lr``, ``pool`` (distinct batches).
"""
from __future__ import annotations

import dataclasses
import functools
import gc
import importlib
import math
import time

import jax
import jax.numpy as jnp

import compare

WIDTHS = {"hidden_size": "d_model", "num_attention_heads": "n_heads",
          "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim",
          "intermediate_size": "d_ff", "vocab_size": "vocab_size",
          "vocab_padded": "vocab_padded", "rope_theta": "rope_theta",
          "rms_norm_eps": "norm_eps"}

# seconds of steps the window keeps queued beyond the one it waits for,
# so that a stall of the host does not idle the chip
AHEAD_S = 6.0


def program_config(c: dict):
    """The program's registered configuration cut to the file's depth;
    every width must equal the file's."""
    from repro.configs import get_config
    full = get_config(c["program_config"])
    for key, attr in WIDTHS.items():
        if getattr(full, attr) != c[key]:
            raise ValueError(f"{c['name']}: program {attr}="
                             f"{getattr(full, attr)!r} != file {key}="
                             f"{c[key]!r}")
    L = c["num_hidden_layers"]
    return dataclasses.replace(
        full, n_layers=L, block_pattern=full.block_pattern[:L],
        ffn_pattern=full.ffn_pattern[:L], remat=c["remat"],
        dtype=c["dtype"], param_dtype=c["param_dtype"])


def make_batches(key, *, n, B, S, vocab):
    """``n`` batches of B uniform-token sequences of length S (labels:
    the next token), each with its own balance permutation."""
    out = []
    for i in range(n):
        k1, k2 = jax.random.split(jax.random.fold_in(key, i))
        ids = jax.random.randint(k1, (B, S + 1), 0, vocab, jnp.int32)
        out.append({"tokens": ids[:, :-1], "labels": ids[:, 1:],
                    "perm": jax.random.permutation(k2, B).astype(jnp.int32)})
    return out


class Runner:
    unit = "tokens"
    # the reference put in the program's place (calibrate.py): the control,
    # one precision below the configuration's bf16, and a planted fault
    VARIANTS = {"control": {"quant": jnp.float8_e4m3fn},
                "half_batch": {"keep": 0.5}}

    def __init__(self, config, traffic, *, cell, seed, devices, log):
        self.c, self.t, self.cell = config, traffic, cell
        self.devices, self.log = devices, log
        self.ref = importlib.import_module(f"reference.{config['family']}")
        self.flops = importlib.import_module(f"flops.{config['family']}")
        self.failed = 0
        S, B, G = traffic["seq_len"], traffic["sequences"], traffic["groups"]
        self.shape = (B, S)
        self.n_groups, self.members = G, B // G
        from repro.core.split import default_plan
        self.split = default_plan(config["num_hidden_layers"]).split_points[-1]
        self.wkey, self.dkey = jax.random.split(compare.seed_key(seed))
        self.leaf_norms = jax.jit(self._leaf_norms)

    def _leaf_norms(self, params, wkey):
        w0 = self.ref.make_weights(wkey, self.c)
        return [jnp.sqrt(jnp.sum(jnp.square(p - w)))
                for p, w in zip(jax.tree.leaves(params), jax.tree.leaves(w0))]

    def _norms(self, params):
        return [float(x) for x in self.leaf_norms(params, self.wkey)]

    def setup(self):
        from repro.core.round_step import (make_s2fl_train_step,
                                           train_step_shardings)
        from repro.launch.mesh import make_mesh
        from repro.launch.steps import train_inputs
        cfg = program_config(self.c)
        B, S = self.shape
        mesh = make_mesh((len(self.devices), 1), ("data", "model"),
                         devices=self.devices)
        step = make_s2fl_train_step(cfg, self.split, self.n_groups,
                                    self.t["lr"], dp_axes=("data",),
                                    group_members=self.members)
        abstract = train_inputs(cfg, batch=B, seq=S)
        in_sh, out_sh = train_step_shardings(cfg, mesh, abstract)
        make = jax.jit(functools.partial(self.ref.make_weights, c=self.c),
                       out_shardings=in_sh[0])
        self.batches = jax.jit(functools.partial(
            make_batches, n=self.t["pool"], B=B, S=S,
            vocab=self.c["vocab_size"]),
            out_shardings=[in_sh[1]] * self.t["pool"])(self.dkey)
        params = make(self.wkey)
        with jax.set_mesh(mesh):
            self.step = jax.jit(step, in_shardings=in_sh,
                                out_shardings=out_sh,
                                donate_argnums=(0,)).lower(
                params, self.batches[0]).compile()
        self.params, self.i, self.waited = params, 0, 0
        losses = []
        for i in range(3):
            t0 = time.perf_counter()
            self.call()
            losses.append(self._last_loss)
            if i == 0:
                n1 = self._norms(self.params)
        self.ahead = max(1, round(AHEAD_S / (time.perf_counter() - t0)))
        self.first = {"losses": losses, "n1": n1,
                      "n3": self._norms(self.params)}
        self.failed = 0
        self.log(f"[fused_step] split {self.split}, {B} x {S} tokens, "
                 f"{self.n_groups} groups; first losses {losses}")

    def dispatch(self):
        """Queue one step on the device; returns its loss, unread."""
        batch = self.batches[self.i % len(self.batches)]
        self.i += 1
        self.params, loss = self.step(self.params, batch)
        return loss

    def wait(self, loss) -> float:
        """Wait for a step dispatched earlier and read its loss; waiting
        for the newest step waits for the parameters too."""
        self._last_loss = float(loss)
        self.waited += 1
        if self.waited == self.i:
            jax.block_until_ready(self.params)
        if not math.isfinite(self._last_loss):
            self.failed += 1
        return float(self.shape[0] * self.shape[1])

    def call(self) -> float:
        return self.wait(self.dispatch())

    def flops_per_unit(self) -> float:
        return self.flops.train_flops_per_token(self.c, self.shape[1])

    def free(self):
        self.params = self.step = None
        gc.collect()

    def reference(self, quant=None, keep=1.0, precision="highest"):
        """The reference's first three steps: losses and leaf norms."""
        step = jax.jit(functools.partial(
            self.ref.s2fl_step, c=self.c, split=self.split,
            n_groups=self.n_groups, members=self.members, lr=self.t["lr"],
            quant=quant, keep=keep), donate_argnums=(0,))
        w = jax.jit(functools.partial(self.ref.make_weights, c=self.c))(
            self.wkey)
        losses = []
        with jax.default_matmul_precision(precision):
            for i in range(3):
                loss, w = step(w, self.batches[i])
                losses.append(float(loss))
                if i == 0:
                    n1 = self._norms(w)
        return {"losses": losses, "n1": n1, "n3": self._norms(w)}

    def check(self) -> list:
        ref = self.reference()
        numbers = compare.training_numbers(self.first, ref)
        self.log(f"[fused_step] program {self.first['losses']} reference "
                 f"{ref['losses']}; numbers {numbers}")
        return compare.checks(numbers, compare.limits_of(self.cell))
