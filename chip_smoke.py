#!/usr/bin/env python3
"""Smoke run of the S²FL trainer on a TPU, checked against the host CPU.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # one host with four chips

One chip: VGG-16, the paper's large CNN, always at full width, trains
through ``repro.launch.train.main`` (the ``python -m repro.launch.train``
entry point, so ``S2FLEngine``) on the paper's federation: seeded
CIFAR-shaped data at CIFAR-10's size (50,000 training images), 100
Table-1 devices, 10 per round, Dirichlet alpha = 0.1, batch 32. At that
size 96 of the 100 devices hold a full batch, so the engine compiles
one program per split rather than one per ragged batch shape. Two
phases:

  (a) sync, fp32;
  (b) semi-async pipeline, int8 uplink, top-k downlink, error feedback,
      fused cohort compression and fused server step, so the
      ``comm_fused`` Pallas kernels run compiled.

Each phase runs on the TPU and then again on the host CPU device of this
same process (``jax.default_device``). The simulated Eq.-1 clock and the
wire bytes must equal the CPU run's exactly, every round. Both runs take
f32 matmuls and convolutions at ``highest`` precision (at the TPU's
default, f32 convolutions take one bf16 pass). Every round's loss must
agree within LOSS_ATOL, a band that a planted fault in the server
gradient failed from round 1 on in phase (a) (PERF.md, Findings). The
final test loss must agree within the same band in phase (a). In phase
(b) it is printed but not held to a band: the int8 and top-k codecs
turn sub-ulp differences into whole quantization steps and swapped
survivors, and on the host CPU alone a 1e-6 relative change of the
initial parameters moved (b)'s final test loss by 0.031 on a v5e host,
three times the band.

Four chips: only the fused SPMD round step
(``core.round_step.make_s2fl_train_step``) for internlm2-1.8b at its
published widths, depth cut to SPMD_LAYERS layers, a few steps on a
(4, 1) data x model mesh, compared with the same steps on one chip.

The last line of standard output is the result,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
The script exits nonzero, and prints no result, when JAX finds no TPU,
when a phase raises, or when a check fails. It starts no processes.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(REPO, "src"))

# the paper's federation (Sec. 5.1), a few rounds
FEDERATION = ["--arch", "vgg16", "--clients", "100", "--per-round", "10",
              "--alpha", "0.1", "--batch-size", "32", "--n-train", "50000",
              "--rounds", "3", "--eval-every", "1000", "--seed", "0"]
# name: (train.main argv, whether the final test loss is held to the band)
PHASES = {
    "a": (FEDERATION, True),
    "b": (FEDERATION + ["--exec-mode", "semi_async", "--pipeline",
                        "--codec", "int8", "--grad-codec", "topk",
                        "--error-feedback", "--fused-comm",
                        "--fused-server"], False),
}
PRECISION = "highest"
LOSS_ATOL = 0.01
WIRE_KEYS = ("clock", "comm", "comm_up", "comm_down", "comm_dispatch")

SPMD_ARCH, SPMD_LAYERS = "internlm2-1.8b", 4
SPMD_BATCH, SPMD_SEQ, SPMD_GROUPS, SPMD_STEPS = 8, 512, 2, 3
SPMD_LOSS_RTOL, SPMD_UPDATE_RTOL = 1e-2, 5e-2


def log(*a):
    print(*a, flush=True)


class CompileClock:
    """Sums JAX's own compile-time events (tracing, lowering, backend
    compilation) so each phase can report its compile seconds."""

    EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "trace",
              "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
              "/jax/core/compile/backend_compile_duration": "compile"}

    def __init__(self):
        import jax
        self.reset()
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_):
        k = self.EVENTS.get(event)
        if k:
            self.secs[k] += secs
            self.count[k] += 1

    def reset(self):
        self.secs = dict.fromkeys(self.EVENTS.values(), 0.0)
        self.count = dict.fromkeys(self.EVENTS.values(), 0)

    def line(self):
        return " ".join(f"{k}_s={self.secs[k]!r}" for k in self.secs) + \
            f" backend_compiles={self.count['compile']}"


def run_phase(name, argv, clock, gate_final=True):
    """Train one phase on the TPU, then on the host CPU; returns a list
    of failed checks."""
    import jax
    from repro.launch import train
    runs = {}
    for where in ("tpu", "cpu"):
        ctx = (jax.default_device(jax.devices("cpu")[0]) if where == "cpu"
               else contextlib.nullcontext())
        clock.reset()
        log(f"== phase ({name}) on {where}: train.main {' '.join(argv)}")
        t0 = time.perf_counter()
        with ctx, jax.default_matmul_precision(PRECISION):
            res = train.main(argv)
        wall = time.perf_counter() - t0
        runs[where] = res
        losses = [h["loss"] for h in res["history"]]
        log(f"[{name}/{where}] sim_clock_s={res['sim_clock_s']!r} "
            f"comm_bytes={res['comm_bytes']!r} losses={losses!r} "
            f"final_test_loss={res['final_loss']!r} wall_s={wall!r} "
            f"{clock.line()}")
    tpu, cpu = runs["tpu"], runs["cpu"]
    wire, loss = [], []
    if len(tpu["history"]) != len(cpu["history"]):
        wire.append(f"({name}) round counts differ")
    for i, (ht, hc) in enumerate(zip(tpu["history"], cpu["history"])):
        wire += [f"({name}) round {i} {k}: tpu {ht[k]!r} != cpu {hc[k]!r}"
                 for k in WIRE_KEYS if ht[k] != hc[k]]
        if abs(ht["loss"] - hc["loss"]) > LOSS_ATOL:
            loss.append(f"({name}) round {i} loss: tpu {ht['loss']!r} "
                        f"vs cpu {hc['loss']!r} outside the band")
    if gate_final and abs(tpu["final_loss"] - cpu["final_loss"]) > LOSS_ATOL:
        loss.append(f"({name}) final test loss: tpu "
                    f"{tpu['final_loss']!r} vs cpu {cpu['final_loss']!r}")
    dl = max(abs(ht["loss"] - hc["loss"])
             for ht, hc in zip(tpu["history"], cpu["history"]))
    log(f"[{name}/check] clock and wire bytes equal every round: "
        f"{not wire}; max |loss diff| {dl!r}, final test loss diff "
        f"{abs(tpu['final_loss'] - cpu['final_loss'])!r}"
        f"{'' if gate_final else ' (not held)'} (band {LOSS_ATOL}, "
        f"{PRECISION} precision)")
    return wire + loss


def one_chip():
    clock = CompileClock()
    fails = []
    for name, (argv, gate_final) in PHASES.items():
        fails += run_phase(name, argv, clock, gate_final)
    return fails


def spmd_config():
    from repro.configs import get_config
    full = get_config(SPMD_ARCH)
    return full, dataclasses.replace(
        full, n_layers=SPMD_LAYERS,
        block_pattern=full.block_pattern[:SPMD_LAYERS],
        ffn_pattern=full.ffn_pattern[:SPMD_LAYERS], remat=True)


def spmd_run(cfg, devices, init, data, *, steps, groups, cohorts):
    """``steps`` fused round steps from the host parameters ``init`` on a
    (len(devices), 1) data x model mesh. Returns (losses, final params
    on the host, compiled HLO text, the devices the batch and the
    parameters landed on)."""
    import jax
    from repro.core.round_step import (make_s2fl_train_step,
                                       train_step_shardings)
    from repro.core.split import default_plan
    from repro.launch.mesh import make_mesh
    from repro.launch.steps import train_inputs
    mesh = make_mesh((len(devices), 1), ("data", "model"), devices=devices)
    split = default_plan(cfg.n_layers).split_points[-1]
    step = make_s2fl_train_step(cfg, split, groups, 0.01,
                                dp_axes=("data",),
                                group_members=cohorts // groups)
    batch, seq = data["tokens"].shape
    in_sh, out_sh = train_step_shardings(
        cfg, mesh, train_inputs(cfg, batch=batch, seq=seq))
    params = jax.device_put(init, in_sh[0])
    b = jax.device_put(data, in_sh[1])
    with jax.set_mesh(mesh):
        compiled = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh,
                           donate_argnums=(0,)).lower(params, b).compile()
    losses = []
    for _ in range(steps):
        params, loss = compiled(params, b)
        losses.append(float(loss))
    where = {
        "batch": sorted({s.device.id for s in b["tokens"].addressable_shards
                         if s.data.size}),
        "params": sorted({d.id for leaf in jax.tree.leaves(params)
                          for d in leaf.sharding.device_set}),
    }
    return losses, jax.device_get(params), compiled.as_text(), where


def spmd(n_chips):
    """The fused SPMD round step on n_chips vs one chip."""
    import jax
    from repro.utils.hlo import collective_bytes
    full, cfg = spmd_config()
    log(f"[spmd] {SPMD_ARCH}: published widths (d_model={cfg.d_model}, "
        f"heads={cfg.n_heads}/{cfg.n_kv_heads}, head_dim={cfg.head_dim}, "
        f"d_ff={cfg.d_ff}, vocab={cfg.vocab_size}); depth cut "
        f"{full.n_layers} -> {cfg.n_layers} layers; batch {SPMD_BATCH} x "
        f"seq {SPMD_SEQ}, {SPMD_GROUPS} balance groups, {SPMD_STEPS} steps")
    rng = np.random.default_rng(0)
    data = {
        "tokens": rng.integers(0, cfg.vocab_size, (SPMD_BATCH, SPMD_SEQ),
                               dtype=np.int32),
        "labels": rng.integers(0, cfg.vocab_size, (SPMD_BATCH, SPMD_SEQ),
                               dtype=np.int32),
        "perm": rng.permutation(SPMD_BATCH).astype(np.int32),
    }
    from repro.models import SplitModel
    init = jax.device_get(SplitModel(cfg).init(jax.random.PRNGKey(0)))
    kw = dict(steps=SPMD_STEPS, groups=SPMD_GROUPS, cohorts=n_chips)
    devs = jax.devices()[:n_chips]
    fails = []
    if len(devs) < n_chips:
        return [f"[spmd] {n_chips} devices asked for, {len(devs)} found"]
    runs = {}
    for n in (1, n_chips):
        t0 = time.perf_counter()
        losses, host, text, where = spmd_run(cfg, devs[:n], init, data,
                                             **kw)
        coll = {k: v for k, v in collective_bytes(text).items()
                if v and not k.startswith("_")}
        log(f"[spmd/{n} chip] losses={losses!r} wall_s="
            f"{time.perf_counter() - t0!r} batch_on={where['batch']} "
            f"params_on={where['params']} collective_bytes={coll}")
        runs[n] = (losses, host)
        if n > 1 and (len(where["batch"]) != n or len(where["params"]) != n):
            fails.append(f"[spmd] the {n}-chip step did not span {n} "
                         f"devices: {where}")
    (l1, p1), (ln, pn) = runs[1], runs[n_chips]
    for i, (a, b) in enumerate(zip(ln, l1)):
        if abs(a - b) > SPMD_LOSS_RTOL * abs(b):
            fails.append(f"[spmd] step {i} loss {a!r} vs one chip {b!r}")
    num = den = 0.0
    for w0, w1, wn in zip(jax.tree.leaves(init), jax.tree.leaves(p1),
                          jax.tree.leaves(pn)):
        d1 = np.asarray(w1, np.float64) - np.asarray(w0, np.float64)
        dn = np.asarray(wn, np.float64) - np.asarray(w0, np.float64)
        num += float(np.sum((dn - d1) ** 2))
        den += float(np.sum(d1 ** 2))
    rel = (num / den) ** 0.5 if den else float("inf")
    log(f"[spmd/check] max |loss diff| "
        f"{max(abs(a - b) for a, b in zip(ln, l1))!r} (rtol "
        f"{SPMD_LOSS_RTOL}); parameter update rel L2 diff {rel!r} "
        f"(limit {SPMD_UPDATE_RTOL})")
    if not rel <= SPMD_UPDATE_RTOL:
        fails.append(f"[spmd] parameter update differs by {rel!r}")
    return fails


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: the VGG-16 engine phases; 4: only the fused "
                         "SPMD round step on a (4, 1) mesh vs one chip")
    args = ap.parse_args(argv)

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX's first device is "
              f"{dev.platform}); nothing was run", file=sys.stderr)
        return 2
    from repro.utils.compile_cache import enable_compile_cache
    log(f"[setup] device {dev.platform} {dev.device_kind} x "
        f"{len(jax.devices())}; compile cache {enable_compile_cache()}")
    fails = spmd(args.chips) if args.chips > 1 else one_chip()
    if fails:
        for f in fails:
            print("FAILED", f, file=sys.stderr)
        return 1
    log(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
