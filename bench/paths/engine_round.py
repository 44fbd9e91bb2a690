"""Path: ``S2FLEngine.run_round`` of the host engine (``core/engine.py``),
rounds back to back in a closed loop.

Set-up generates the federation (``data.py``), builds the engine from the
traffic's settings, gives it weights made from the seed in one jitted
call (``reference/<family>.py``), and snapshots its whole state
(``checkpoint.save_run_state``). The first three rounds are kept for the
check: their losses, the leaf norms of ``w1 - w0`` and ``w3 - w0``, and
what the engine decided in each (participants, splits, balance groups,
commits), read at the driver's public ``run_round`` and its ``execute``
callback (read in every round: it also counts the samples a round
trains, as devices with fewer than a batch train all they hold). Warm-up rounds follow until the engine has run comfortably more
rounds than the window will hold, so that every program the window calls
is compiled; then the snapshot is restored and the window replays the
same rounds from round one. The federation, device grid and round
schedule come from the traffic's fixed ``federation_seed``; the run's
seed draws the images and the weights, so every seed meets the same
shapes.

The check frees the engine, replays the three rounds' batch draws (the
engine's own protocol: participants, then one ``choice`` per member, group
by group) and runs the plain reference over them, with the engine's
commits, at ``highest`` precision.
"""
from __future__ import annotations

import functools
import gc
import importlib
import math
import os
import tempfile

import jax
import numpy as np

import compare
import data as bench_data


def leaf_norms(a, b) -> list:
    return [float(np.linalg.norm(np.asarray(x, np.float64)
                                 - np.asarray(y, np.float64)))
            for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))]


class Runner:
    unit = "samples"
    # the reference put in the program's place (calibrate.py): the control,
    # one precision below the configuration's f32 at 'highest', and a
    # planted fault
    VARIANTS = {"control": {"precision": "high"},
                "half_batch": {"keep": 0.5}}

    def __init__(self, config, traffic, *, cell, seed, devices, log):
        self.c, self.t, self.cell = config, traffic, cell
        self.seed, self.log = seed, log
        self.ref = importlib.import_module(f"reference.{config['family']}")
        self.flops = importlib.import_module(f"flops.{config['family']}")
        self.failed = 0

    def _engine(self):
        from repro.configs import get_config
        from repro.configs.base import CommConfig, DriverConfig
        from repro.core.engine import EngineConfig, S2FLEngine
        from repro.models import SplitModel
        cfg = get_config(self.c["program_config"])
        if [list(s) for s in cfg.stages] != self.c["stages"] or \
                cfg.n_classes != self.c["n_classes"]:
            raise ValueError(f"program {cfg.name} differs from the file")
        t = self.t
        ecfg = EngineConfig(
            mode="s2fl", use_balance=True, use_sliding=True,
            clients_per_round=t["per_round"], local_steps=1,
            batch_size=t["batch_size"], lr=t["lr"],
            group_size=t["group_size"], split_k=t["split_k"],
            seed=t["federation_seed"], n_classes=self.c["n_classes"],
            comm=CommConfig(**t["comm"]), driver=DriverConfig(**t["driver"]),
            fused_comm=t["fused_comm"], fused_server=t["fused_server"])
        return S2FLEngine(SplitModel(cfg), self.fed, ecfg)

    def setup(self):
        from repro.checkpoint import save_run_state
        jax.config.update("jax_default_matmul_precision",
                          self.c["matmul_precision"])
        self.fed = bench_data.federation(self.c, self.t, self.seed)
        self.wkey = compare.seed_key(self.seed)
        w0 = jax.jit(lambda k: self.ref.make_weights(k, self.c))(self.wkey)
        self.w0 = jax.device_get(w0)
        self.engine = eng = self._engine()
        eng.params = w0
        fd, self.snapshot = tempfile.mkstemp(suffix=".npz")
        os.close(fd)
        save_run_state(self.snapshot, eng)

        self.decisions, losses, norms = [], [], {}
        eng.driver.run_round = self._tap(eng.driver.run_round)
        for r in range(3):
            losses.append(self._round())
            if r in (0, 2):
                norms[r] = leaf_norms(jax.device_get(eng.params), self.w0)
        self.first = {"losses": losses, "n1": norms[0], "n3": norms[2]}

        # the rest of the first cycle: every program the window calls is
        # compiled here, as the window replays this cycle's rounds
        for _ in range(3, self.t["cycle_rounds"]):
            self._round()
        self._restore()
        self.failed = 0
        self.log(f"[engine_round] first losses {losses}")

    def _restore(self):
        from repro.checkpoint import restore_run_state
        restore_run_state(self.snapshot, self.engine)
        self.k = 0

    def _tap(self, orig):
        """The driver's run_round, also recording what it decided."""
        def run_round(participants, execute=None, **kw):
            seen = {}

            def ex(splits):
                report = execute(splits)
                seen["splits"] = dict(splits)
                seen["groups"] = {g: tuple(m) for g, m in
                                  report["groups"].items()}
                return report
            rec = orig(participants, execute=ex, **kw)
            self.decisions.append({
                "participants": [int(p) for p in participants],
                "splits": {int(k): int(v) for k, v in seen["splits"].items()},
                "groups": seen["groups"],
                "committed": [int(g) for g in rec.committed]})
            return rec
        return run_round

    def _round(self) -> float:
        rec = self.engine.run_round()
        jax.block_until_ready(self.engine.params)
        if not math.isfinite(rec["loss"]):
            self.failed += 1
        return rec["loss"]

    def call(self) -> float:
        if self.k == self.t["cycle_rounds"]:
            self._restore()
        self.k += 1
        self._round()
        b = self.t["batch_size"]
        return float(sum(min(b, len(self.fed[c]["y"]))
                         for c in self.decisions[-1]["splits"]))

    def flops_per_unit(self) -> float:
        return self.flops.train_flops_per_sample(self.c)

    def free(self):
        self.engine = None
        gc.collect()
        if os.path.exists(self.snapshot):
            os.remove(self.snapshot)

    # ---------------------------------------------------------- check
    def _rounds(self):
        """The three rounds' groups as the reference takes them, {gid:
        [(cid, split, x, y, data size)]}: replays the engine's draws from
        its seeded generator, and checks that the participants drawn are
        the ones the engine used."""
        rng = np.random.default_rng(self.t["federation_seed"])
        cids = sorted(self.fed)
        b = self.t["batch_size"]
        rounds = []
        for d in self.decisions[:3]:
            drawn = [int(p) for p in rng.choice(
                cids, size=min(self.t["per_round"], len(cids)),
                replace=False)]
            if drawn != d["participants"]:
                raise ValueError(f"participants {d['participants']} are not "
                                 f"the seeded draw {drawn}")
            groups = {}
            for gid in sorted(d["groups"]):
                groups[gid] = []
                for cid in d["groups"][gid]:
                    dev = self.fed[cid]
                    n = len(dev["y"])
                    idx = rng.choice(n, size=min(b, n), replace=n < b)
                    groups[gid].append((cid, d["splits"][cid], dev["x"][idx],
                                        dev["y"][idx], float(n)))
            rounds.append(groups)
        return rounds

    def reference(self, keep=1.0, precision="highest"):
        """The reference's first three rounds, with the engine's commits:
        losses and leaf norms."""
        comm = self.t["comm"]
        link = functools.partial(self.ref.Link,
                                 frac=comm.get("topk_frac", 0.1),
                                 feedback=comm.get("error_feedback", False))
        up = link(comm["codec"])
        down = link(comm.get("grad_codec") or comm["codec"])
        w = jax.tree.map(jax.numpy.asarray, self.w0)
        held, losses, norms = {}, [], {}
        with jax.default_matmul_precision(precision):
            for r, groups in enumerate(self._rounds()):
                res = self.ref.train_groups(w, groups, self.c, self.t["lr"],
                                            up, down, keep=keep)
                trained = sum(len(m) for m in groups.values())
                losses.append(sum(v[0] for v in res.values()) / trained)
                held.update(res)
                done = [held.pop(g) for g in self.decisions[r]["committed"]]
                if done:
                    w = self.ref.aggregate(done, self.c)
                if r in (0, 2):
                    norms[r] = leaf_norms(jax.device_get(w), self.w0)
        return {"losses": losses, "n1": norms[0], "n3": norms[2]}

    def check(self) -> list:
        ref = self.reference()
        numbers = compare.training_numbers(self.first, ref)
        self.log(f"[engine_round] program {self.first['losses']} reference "
                 f"{ref['losses']}; numbers {numbers}")
        return compare.checks(numbers, compare.limits_of(self.cell))
