"""Kernel micro-benchmarks: wall time of the Pallas kernels (interpret
mode on CPU — correctness-representative, not perf-representative; real
perf comes from the dry-run roofline) vs their pure-jnp oracles, plus
the batched cohort-compression hot path vs the sequential per-device
codec loop it replaces.

Every row carries the oracle/sequential comparator in the derived
column; the fused-vs-sequential rows also carry an explicit ``speedup``
so the CI artifact (``--out`` JSON) makes perf-ordering regressions
diffable per PR. The fused rows time the REAL dispatch path — backend
selection included (jnp oracle off-TPU, compiled Pallas on TPU) and the
``jnp.stack`` cohort assembly inside the timed region, since that is
the cost the engine actually pays per direction."""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp

from benchmarks.common import Timer, emit

KEY = jax.random.PRNGKey(0)
ITERS = 10               # default; override with --iters
WARMUP = 2


def _bench(fn, *args, iters=None):
    iters = ITERS if iters is None else iters
    for _ in range(WARMUP):
        jax.block_until_ready(fn(*args))
    with Timer() as t:
        for _ in range(iters):
            jax.block_until_ready(fn(*args))
    return t.us / iters


def _speedup(us_base, us_new) -> str:
    return f"speedup={us_base / us_new:.2f}x"


def run_model_kernels():
    from repro.kernels.flash_attention.kernel import flash_attention_fwd
    from repro.kernels.flash_attention.ref import attention_ref
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (8, 512, 64))
    k = jax.random.normal(ks[1], (4, 512, 64))
    v = jax.random.normal(ks[2], (4, 512, 64))
    us_k = _bench(jax.jit(lambda q, k, v: flash_attention_fwd(
        q, k, v, causal=True, groups=2, interpret=True)), q, k, v)
    us_r = _bench(jax.jit(lambda q, k, v: attention_ref(
        q, k, v, causal=True, groups=2)), q, k, v)
    emit("kern.flash_attn.8x512x64", us_k, f"ref_us={us_r:.0f}")

    from repro.kernels.ssd_scan.kernel import ssd_scan_pallas
    from repro.models.ssm import ssd_scan_ref
    b, s, h, p, n = 2, 512, 4, 64, 32
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)))
    B = jax.random.normal(ks[3], (b, s, n))
    C = jax.random.normal(ks[4], (b, s, n))
    us_k = _bench(jax.jit(lambda *a: ssd_scan_pallas(
        *a, chunk=128, interpret=True)), x, dt, A, B, C)
    us_r = _bench(jax.jit(lambda *a: ssd_scan_ref(*a, chunk=128)),
                  x, dt, A, B, C)
    emit("kern.ssd_scan.2x512x4x64", us_k, f"ref_us={us_r:.0f}")

    from repro.kernels.moe_gmm.kernel import held_experts_gmm
    from repro.kernels.moe_gmm.ref import ragged
    ks = jax.random.split(KEY, 2)
    xg = jax.random.normal(ks[0], (2048, 256)) * 0.5
    wg = jax.random.normal(ks[1], (8, 256, 512)) * 0.05
    sizes = jnp.full((8,), 192, jnp.int32)
    us_k = _bench(jax.jit(lambda *a: held_experts_gmm(*a, interpret=True)),
                  xg, wg, sizes)
    us_r = _bench(jax.jit(ragged), xg, wg, sizes)
    emit("kern.held_experts_gmm.2048x256x512x8", us_k, f"ref_us={us_r:.0f}")


def run_comm_kernels():
    """The wire kernels: the int8 quantize/dequantize pair, the fused
    single-pass roundtrips, and the batched cohort call vs the
    per-device loop it replaces in the engine."""
    from repro.kernels.int8_quant.kernel import (int8_dequantize_pallas,
                                                 int8_quantize_pallas)
    from repro.kernels.int8_quant.ref import (int8_dequantize_ref,
                                              int8_quantize_ref)
    rows = jax.random.normal(KEY, (2048, 256)) * 2.0

    def pair_pallas(x):
        q, s, z = int8_quantize_pallas(x, interpret=True)
        return int8_dequantize_pallas(q, s, z, interpret=True)

    def pair_ref(x):
        q, s, z = int8_quantize_ref(x)
        return int8_dequantize_ref(q, s, z)

    us_k = _bench(jax.jit(pair_pallas), rows)
    us_r = _bench(jax.jit(pair_ref), rows)
    emit("kern.int8_pair.2048x256", us_k, f"ref_us={us_r:.0f}")

    # the fused single-kernel roundtrip vs the same two-kernel pair
    from repro.kernels.comm_fused.kernel import (int8_roundtrip_pallas,
                                                 sparse_combine_pallas)
    from repro.kernels.comm_fused.ref import (int8_roundtrip_ref,
                                              sparse_combine_ref)
    us_k = _bench(lambda x: int8_roundtrip_pallas(x, interpret=True),
                  rows)
    us_r = _bench(jax.jit(int8_roundtrip_ref), rows)
    emit("kern.fused_int8_rt.2048x256", us_k, f"ref_us={us_r:.0f}")

    d, n = 16, 16384
    y = jax.random.normal(KEY, (d, n))
    mask = (jax.random.uniform(jax.random.fold_in(KEY, 1), (d, n))
            < 0.1).astype(jnp.float32)
    us_k = _bench(lambda *a: sparse_combine_pallas(
        *a, 1.0, interpret=True), y, mask)
    us_r = _bench(jax.jit(lambda *a: sparse_combine_ref(*a, 1.0)),
                  y, mask)
    emit(f"kern.sparse_combine.{d}x{n}", us_k, f"ref_us={us_r:.0f}")


def run_cohort_vs_sequential():
    """The engine-level contest the fused path exists for: ONE batched
    (D, N) call per direction vs D per-device codec roundtrips. Both
    sides run their real dispatch (backend-selected kernel vs the
    per-device jnp chain); the fused side pays its jnp.stack cohort
    assembly inside the timed region."""
    from repro.comm.codecs import get_codec
    from repro.kernels.comm_fused import (fused_int8_roundtrip,
                                          fused_sparse_roundtrip)
    d, n = 16, 32768
    parts = [jax.random.normal(jax.random.fold_in(KEY, i), (n,))
             for i in range(d)]

    int8 = get_codec("int8")
    us_f = _bench(lambda: fused_int8_roundtrip(jnp.stack(parts), None)[0])
    us_s = _bench(lambda: [int8.roundtrip(p)[0] for p in parts])
    emit(f"comm.cohort_int8.{d}x{n}", us_f,
         f"seq_us={us_s:.0f} {_speedup(us_s, us_f)}")

    topk = get_codec("topk", topk_frac=0.1)
    k = max(1, -(-n // 10))
    us_f = _bench(lambda: fused_sparse_roundtrip(jnp.stack(parts), None,
                                                 k=k, scale=1.0)[0])
    us_s = _bench(lambda: [topk.roundtrip(p)[0] for p in parts])
    emit(f"comm.cohort_topk.{d}x{n}", us_f,
         f"seq_us={us_s:.0f} {_speedup(us_s, us_f)}")

    # error-feedback variant: residual add + update fused into the same
    # call vs the channel's separate add / subtract around each encode
    res = [jax.random.normal(jax.random.fold_in(KEY, 100 + i), (n,))
           * 0.1 for i in range(d)]

    def seq_ef():
        outs = []
        for p, r in zip(parts, res):
            y = p + r
            out, _ = int8.roundtrip(y)
            outs.append((out, y - out))
        return outs

    us_f = _bench(lambda: fused_int8_roundtrip(jnp.stack(parts),
                                               jnp.stack(res)))
    us_s = _bench(seq_ef)
    emit(f"comm.cohort_int8_ef.{d}x{n}", us_f,
         f"seq_us={us_s:.0f} {_speedup(us_s, us_f)}")


def run():
    run_model_kernels()
    run_comm_kernels()
    run_cohort_vs_sequential()


def main(argv=None):
    global ITERS
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--iters", type=int, default=ITERS,
                    help="timed iterations per row (after "
                         f"{WARMUP} warmup calls)")
    ap.add_argument("--out", default=None,
                    help="also dump every emitted row to this JSON "
                         "path (CI uploads it as BENCH_kernels.json)")
    args = ap.parse_args(argv)
    ITERS = args.iters
    run()
    if args.out:
        from benchmarks.common import write_json
        write_json(args.out)


if __name__ == "__main__":
    main()
