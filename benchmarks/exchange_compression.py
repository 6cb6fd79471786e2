"""Wire-format benchmark: raw vs packed exchange encodings, and the
codec that produces them (§3.2.1).

The exchange layer can ship its request buckets either as raw int32 keys +
a separate bool-mask all-to-all, or as the packed wire format (EF-coded
keys at catalog-derived widths, mask folded in, bitset replies).  This
benchmark proves the reduction FROM THE LOWERED HLO — the all-to-all
operand bytes of the compiled SPMD plan — on the q4/q18 semi-join
exchanges (the Q4/Q18 shapes forced through the §3.2.2 request exchange),
and checks that every lowered plan still matches its numpy oracle under
``wire="packed"`` on both collective backends.

The comparison is three-way: raw wire, packed wire on the baseline XLA
scatter/gather codec (``ops.use_kernels(False)``), and packed wire on
the kernel codec (the gather-light formulation behind the Pallas lane
kernels — the default).  Compression that only shrinks bytes is not
enough (Rödiger et al.): the packed-kernel column must also be FAST.

Acceptance: packed reduces all-to-all bytes by >= 4x AND the
packed-kernel latency is <= 1.05x raw on q4_sj/q18_sj.  A codec
microbenchmark (encode/decode rows/s per packed width) lands in
``experiments/bench/codec_microbench.json``; the three-way table in
``experiments/bench/exchange_compression.json`` (schema is a superset
of the old raw/packed one: the ``codec`` column is additive).

  PYTHONPATH=src python -m benchmarks.exchange_compression --sf 0.02
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import sys

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import time

import jax
import numpy as np

import jax.numpy as jnp

from benchmarks.common import emit, timeit
from repro.core import plans as plan_registry
from repro.core.compression import ef_params
from repro.kernels import ops
from repro.launch.roofline import parse_collective_bytes
from repro.query.lower import lower
from repro.tpch import queries as tq
from repro.tpch import reference
from repro.tpch.driver import TPCHDriver
from repro.tpch.schema import DEFAULT_PARAMS as DP

GATE_REDUCTION = 4.0
GATE_LATENCY = 1.05   # packed-kernel warm latency vs raw, median ratio
SJ_QTY = 250.0  # q18_sj volume threshold (low enough to keep survivors)

# the oracle-parity set: every lowered-IR query with a numpy oracle
PARITY = ("q1", "q4", "q6", "q18")
BACKENDS = ("xla", "one_factor")


def _compile(driver, q, *, wire: str, backend: str = "xla"):
    """Lower + compile one IR query under an explicit wire format/backend
    (bypassing the driver's cached context)."""
    plan = lower(q, driver.catalog, wire=wire)
    ctx = dataclasses.replace(driver.ctx, wire=wire, backend=backend)
    return driver.cluster.compile(plan, ctx, driver.placed)


def _collectives(fn, cols):
    return parse_collective_bytes(fn.lower(cols).compile().as_text())


def _clock(fn, cols) -> float:
    t0 = time.perf_counter()
    jax.block_until_ready(fn(cols))
    return time.perf_counter() - t0


def codec_microbench(repeat: int = 20, capacity: int = 4096, seed: int = 0):
    """Codec throughput in isolation (no exchange, no collectives):
    encode/decode keys/s per packed width, baseline XLA scatter codec
    ("xla" = ref.py, what ``use_kernels(False)`` selects) vs the kernel
    codec (gather-light formulation / Pallas lanes).  Synthetic sorted
    buckets, 8 destinations, 3/4 fill — the §3.2.2 request shape."""
    rng = np.random.default_rng(seed)
    P = 8
    n_valid = capacity * 3 // 4
    mask = np.broadcast_to(np.arange(capacity)[None, :] < n_valid,
                           (P, capacity))
    impls = (("xla", "ref"), ("kernel", ops._codec_impl()))
    rows, ok = [], True
    for domain in (8, 64, 512, 4096):  # l = 0, 2, 5, 8 low bits
        l, uw, lw = ef_params(capacity, domain)
        # row d holds sorted per-destination offsets rebased into d's
        # owned key range [d*domain, (d+1)*domain) — the encoder contract
        keys = (np.sort(rng.integers(0, domain, size=(P, capacity)), axis=1)
                + np.arange(P)[:, None] * domain)
        buckets = jnp.asarray(np.where(mask, keys, 0), dtype=jnp.int32)
        bmask = jnp.asarray(mask)
        for codec, impl in impls:
            t_enc, words = timeit(
                lambda b, m: ops._ef_encode(b, m, domain=domain, impl=impl),
                buckets, bmask, repeat=repeat)
            t_dec, (dkeys, dmask) = timeit(
                lambda w: ops._ef_decode(w, jnp.int32(0), capacity=capacity,
                                         domain=domain, impl=impl),
                words, repeat=repeat)
            # my_base=0 -> the decoder returns per-destination offsets
            offs = keys - np.arange(P)[:, None] * domain
            parity = (np.array_equal(np.asarray(dmask), mask)
                      and np.array_equal(np.where(mask, np.asarray(dkeys), 0),
                                         np.where(mask, offs, 0)))
            ok &= parity
            rows.append({
                "domain": domain, "l_bits": l, "capacity": capacity,
                "words_per_dest": uw + lw, "codec": codec,
                "encode_keys_per_s": P * capacity / max(t_enc, 1e-12),
                "decode_keys_per_s": P * capacity / max(t_dec, 1e-12),
                "parity_ok": parity,
            })
    emit("codec_microbench", rows,
         ["domain", "l_bits", "capacity", "words_per_dest", "codec",
          "encode_keys_per_s", "decode_keys_per_s", "parity_ok"])
    return rows, ok


def run(sf: float = 0.02, repeat: int = 30, seed: int = 0):
    driver = TPCHDriver(sf=sf, seed=seed)
    cols = {n: t.columns for n, t in driver.placed.items()}

    targets = [
        ("q4_sj", tq.q4_sj_ir(alt="request"),
         np.asarray(driver.oracle("q4"), np.float64),
         lambda out: np.asarray(out["value"], np.float64)[:, 0]),
        ("q18_sj", tq.q18_sj_ir(alt="request", qty=SJ_QTY),
         reference.q18_sj(driver.tables, SJ_QTY, DP.q3_segment),
         lambda out: np.asarray(out["value"], np.float64).reshape(-1)),
    ]

    # (label, wire, codec column, kernel codec enabled while tracing)
    variants = (("raw", "raw", "none", True),
                ("packed_xla", "packed", "xla", False),
                ("packed_kernel", "packed", "kernel", True))

    rows, ok = [], True
    for name, q, oracle, extract in targets:
        fns, coll, outs = {}, {}, {}
        for label, wire, _, kern in variants:
            # the codec impl is resolved while TRACING (static jit arg),
            # so compile + first execution + HLO lowering all happen under
            # the toggle; the traced fn keeps its codec afterwards
            ops.use_kernels(kern)
            try:
                fn = _compile(driver, q, wire=wire)
                coll[label] = _collectives(fn, cols)
                out = jax.tree.map(np.asarray, fn(cols))
            finally:
                ops.use_kernels(True)
            assert not out.get("overflow", False), f"{name}/{label} overflowed"
            fns[label] = fn
            outs[label] = extract(out)
        by_kind = {lb: coll[lb].by_kind() for lb in fns}
        a2a = {lb: by_kind[lb].get("all-to-all", {}).get("bytes", 0)
               for lb in fns}
        reduction = a2a["raw"] / max(a2a["packed_kernel"], 1)
        # paired warm latencies: median of back-to-back ratios (robust to
        # host drift, same protocol as benchmarks/ir_overhead.py)
        for fn in fns.values():
            jax.block_until_ready(fn(cols))
        raw_times = []
        ratios = {"packed_xla": [], "packed_kernel": []}
        for _ in range(max(repeat, 5)):
            r = _clock(fns["raw"], cols)
            raw_times.append(r)
            for lb in ratios:
                ratios[lb].append(_clock(fns[lb], cols) / r)
        raw_ms = min(raw_times) * 1e3
        med = {lb: sorted(v)[len(v) // 2] for lb, v in ratios.items()}
        kernel_ratio = med["packed_kernel"]
        oracle_ok = all(np.allclose(outs[lb], oracle, rtol=1e-4)
                        for lb in fns)
        ok &= (oracle_ok and reduction >= GATE_REDUCTION
               and kernel_ratio <= GATE_LATENCY)
        for label, wire, codec, _ in variants:
            rows.append({
                "query": name, "wire": wire, "codec": codec,
                "all_to_all_bytes": a2a[label],
                "all_to_all_count": by_kind[label].get("all-to-all",
                                                       {}).get("count", 0),
                # labeled per-kind breakdown (CollectiveStats.by_kind): the
                # non-all-to-all collectives are invariant across wires, so
                # a reduction that moved bytes to another kind would show
                "collectives": " ".join(
                    f"{k}:{v['bytes']}Bx{v['count']}"
                    for k, v in by_kind[label].items()),
                "latency_ms": raw_ms if label == "raw"
                else raw_ms * med[label],
                "vs_raw_x": 1.0 if label == "raw" else med[label],
                "reduction_x": 1.0 if label == "raw" else reduction,
                "oracle_ok": oracle_ok,
            })
    emit("exchange_compression", rows,
         ["query", "wire", "codec", "all_to_all_bytes", "all_to_all_count",
          "collectives", "latency_ms", "vs_raw_x", "reduction_x",
          "oracle_ok"])

    # oracle parity of the standard lowered queries under packed wire, on
    # both collective backends (one_factor lowers all-to-all to ppermutes)
    parity_rows = []
    for name in PARITY:
        q = plan_registry.get(name).ir
        ref = driver.oracle(name)
        for backend in BACKENDS:
            out = jax.tree.map(
                np.asarray,
                _compile(driver, q, wire="packed", backend=backend)(cols),
            )
            if name == "q18":
                ov, okeys = ref
                n = int(out["valid"].sum())
                match = (n == int(np.isfinite(ov).sum())
                         and np.allclose(out["values"][:n], ov[:n],
                                         rtol=2e-3, atol=1e-2)
                         and np.array_equal(out["keys"][:n], okeys[:n]))
            elif name == "q4":
                match = np.array_equal(out["value"][:, 0], ref)
            else:
                match = np.allclose(np.asarray(out["value"]).reshape(np.shape(ref)),
                                    ref, rtol=2e-4)
            ok &= bool(match)
            parity_rows.append({"query": name, "backend": backend,
                                "wire": "packed", "oracle_ok": bool(match)})
    emit("exchange_compression_parity", parity_rows,
         ["query", "backend", "wire", "oracle_ok"])

    worst = min(r["reduction_x"] for r in rows if r["codec"] == "kernel")
    slowest = max(r["vs_raw_x"] for r in rows if r["codec"] == "kernel")
    status = "OK" if ok else "FAILED"
    print(f"\npacked wire all-to-all reduction: {worst:.1f}x "
          f"(>= {GATE_REDUCTION:.0f}x target), packed-kernel latency "
          f"{slowest:.2f}x raw (<= {GATE_LATENCY:.2f}x target), oracle "
          f"parity on {'/'.join(PARITY)} x {'/'.join(BACKENDS)}: {status}")
    return rows, parity_rows, ok


if __name__ == "__main__":
    p = argparse.ArgumentParser()
    p.add_argument("--sf", type=float, default=0.02)
    p.add_argument("--repeat", type=int, default=30)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--skip-microbench", action="store_true")
    args = p.parse_args()
    _, _, ok = run(sf=args.sf, repeat=args.repeat, seed=args.seed)
    if not args.skip_microbench:
        _, micro_ok = codec_microbench(seed=args.seed)
        ok = ok and micro_ok
    sys.exit(0 if ok else 1)
