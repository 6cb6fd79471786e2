"""Jit'd public wrappers for the Pallas kernels.

On TPU the kernels compile natively; everywhere else (this CPU container,
unit tests) they execute in interpret mode against the same BlockSpec
schedule.  ``use_kernels(False)`` (or REPRO_NO_KERNELS=1) falls back to the
pure-jnp oracles in ref.py — plans call through these wrappers only.
"""
from __future__ import annotations

import functools
import os

import jax

from repro.kernels import (
    bitset_pack,
    grouped_agg,
    mbit_codec,
    ref,
    topk_select,
    wire_codec,
)
from repro.kernels import clustered_sum as clustered_sum_kernel
from repro.kernels import compact as compact_kernel
from repro.kernels import scan_filter as scan_filter_kernel

_FORCE_REF = os.environ.get("REPRO_NO_KERNELS", "0") == "1"
_USE_KERNELS = not _FORCE_REF


def use_kernels(enable: bool) -> None:
    global _USE_KERNELS
    _USE_KERNELS = enable and not _FORCE_REF


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


@functools.partial(jax.jit, static_argnames=("cutoff", "num_groups", "block"))
def filtered_group_sum(measures, groups, pred, *, cutoff, num_groups, block=2048):
    if not _USE_KERNELS:
        return ref.filtered_group_sum(measures, groups, pred, cutoff, num_groups)
    return grouped_agg.filtered_group_sum(
        measures, groups, pred, cutoff, num_groups, block=block,
        interpret=_interpret(),
    )


@functools.partial(jax.jit, static_argnames=("num_keys", "fanout"))
def clustered_sum(values, keys, block_starts, *, num_keys, fanout):
    if not _USE_KERNELS:
        return ref.clustered_sum(values, keys, num_keys)
    return clustered_sum_kernel.clustered_sum(
        values, keys, block_starts, num_keys=num_keys, fanout=fanout,
        interpret=_interpret(),
    )


@functools.partial(jax.jit, static_argnames=("cap",))
def compact_rows(words, offsets, *, cap):
    """The first ``cap`` row indices of the set bits of a packed mask, in
    the kernel's slot order; slots past them are undefined (``offsets``:
    ``compact.plane_offsets`` of the same words)."""
    if not _USE_KERNELS:
        return ref.compact_rows(words, cap)
    return compact_kernel.compact_rows(words, offsets, cap=cap,
                                       interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("k", "block"))
def block_topk(values, keys, *, k, mask=None, block=4096):
    if not _USE_KERNELS:
        return ref.block_topk(values, keys, k, mask, block)
    return topk_select.block_topk(
        values, keys, k, mask, block=block, interpret=_interpret()
    )


@functools.partial(jax.jit, static_argnames=("value", "block"))
def predicate_bitset(column, *, value, block=8192):
    if not _USE_KERNELS:
        return ref.predicate_bitset(column, value)
    return bitset_pack.predicate_bitset(
        column, value, block=block, interpret=_interpret()
    )


@functools.partial(jax.jit, static_argnames=("m", "group"))
def mbit_encode(q, *, m, group):
    if not _USE_KERNELS:
        return ref.mbit_encode(q, m, group)
    return mbit_codec.encode(q, m, group, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("m", "group"))
def mbit_decode_bounds(words, shifts, *, m, group):
    return mbit_codec.decode_bounds(words, shifts, m, group)


# ---------------------------------------------------------------------------
# wire codec (§3.2.1): EF bucket encode/decode + mask fold/unfold
#
# The Pallas lane kernels compile only on real accelerator backends
# (interpret mode is Python per grid step — orders of magnitude too slow
# for the exchange latency budget).  On CPU the kernel path IS the
# gather-light XLA formulation in wire_codec.py, which is what the
# latency gate measures; parity tests exercise the Pallas kernels in
# interpret mode directly against ref.py.
# ---------------------------------------------------------------------------


def _codec_impl() -> str:
    """'ref' | 'xla' | 'pallas' — resolved at CALL time so the benchmark's
    use_kernels() toggle selects a distinct jit cache entry (the impl is a
    static argument of the jitted workers below, never a baked-in global)."""
    if not _USE_KERNELS:
        return "ref"
    return "pallas" if not _interpret() else "xla"


@functools.partial(jax.jit, static_argnames=("domain", "impl"))
def _ef_encode(buckets, bucket_mask, *, domain, impl):
    if impl == "ref":
        return ref.ef_encode(buckets, bucket_mask, domain)
    return wire_codec.ef_encode(
        buckets, bucket_mask, domain, use_pallas=impl == "pallas"
    )


@functools.partial(jax.jit, static_argnames=("capacity", "domain", "impl"))
def _ef_decode(words, my_base, *, capacity, domain, impl):
    if impl == "ref":
        return ref.ef_decode(words, capacity, domain, my_base)
    return wire_codec.ef_decode(
        words, capacity, domain, my_base, use_pallas=impl == "pallas"
    )


@functools.partial(jax.jit, static_argnames=("impl",))
def _mask_fold(mask, *, impl):
    if impl == "ref":
        return ref.mask_fold(mask)
    return wire_codec.mask_fold(mask, use_pallas=impl == "pallas")


@functools.partial(jax.jit, static_argnames=("n", "impl"))
def _mask_unfold(words, *, n, impl):
    if impl == "ref":
        return ref.mask_unfold(words, n)
    return wire_codec.mask_unfold(words, n, use_pallas=impl == "pallas")


def ef_encode(buckets, bucket_mask, *, domain):
    return _ef_encode(buckets, bucket_mask, domain=domain, impl=_codec_impl())


def ef_decode(words, my_base, *, capacity, domain):
    return _ef_decode(words, my_base, capacity=capacity, domain=domain,
                      impl=_codec_impl())


def mask_fold(mask):
    return _mask_fold(mask, impl=_codec_impl())


def mask_unfold(words, *, n):
    return _mask_unfold(words, n=n, impl=_codec_impl())


# ---------------------------------------------------------------------------
# predicate-on-packed scan (compressed residency): code-space range test
# over bit-packed resident words, emitting a validity bitset.  Same
# dispatch discipline as the wire codec — the SWAR formulation is pure XLA
# on CPU, a Pallas lane kernel on TPU, and ref.py decodes-then-compares.
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("rows", "padded_rows", "width",
                                             "negate", "impl"))
def _scan_filter(words, lo, hi, *, rows, padded_rows, width, negate, impl):
    if impl == "ref":
        return ref.scan_filter(words, lo, hi, rows, padded_rows, width, negate)
    if impl == "pallas":
        return scan_filter_kernel.scan_filter_pallas(
            words, lo, hi, rows=rows, padded_rows=padded_rows, width=width,
            negate=negate, interpret=_interpret())
    return scan_filter_kernel.scan_filter_xla(
        words, lo, hi, rows=rows, padded_rows=padded_rows, width=width,
        negate=negate)


def scan_filter(words, lo, hi, *, rows, padded_rows, width, negate=False):
    """Validity bitset of ``lo <= code <= hi`` (optionally negated) over a
    packed word stream; rows past ``rows`` are invalid."""
    return _scan_filter(words, lo, hi, rows=rows, padded_rows=padded_rows,
                        width=width, negate=negate, impl=_codec_impl())


# ---------------------------------------------------------------------------
# flash attention (custom_vjp: Pallas fwd + Pallas bwd) — §Perf optimization
# ---------------------------------------------------------------------------


def _fit_block(S: int, target: int) -> int:
    c = min(target, S)
    while S % c:
        c -= 1
    return c


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_grouped(qg, kg, vg, causal, window, prefix, bq, bk):
    from repro.kernels import flash_attention as FA

    out, _ = FA.flash_attention_fwd_grouped(
        qg, kg, vg, causal=causal, window=window, prefix=prefix,
        bq=bq, bk=bk, interpret=_interpret())
    return out


def _flash_fwd(qg, kg, vg, causal, window, prefix, bq, bk):
    from repro.kernels import flash_attention as FA

    out, lse = FA.flash_attention_fwd_grouped(
        qg, kg, vg, causal=causal, window=window, prefix=prefix,
        bq=bq, bk=bk, interpret=_interpret())
    return out, (qg, kg, vg, out, lse)


def _flash_bwd(causal, window, prefix, bq, bk, res, do):
    from repro.kernels import flash_attention_bwd as FB

    qg, kg, vg, out, lse = res
    dq, dk, dv = FB.flash_attention_bwd(
        qg, kg, vg, out, lse, do, causal=causal, window=window,
        prefix=prefix, bq=bq, bk=bk, interpret=_interpret())
    return dq, dk, dv


_flash_grouped.defvjp(_flash_fwd, _flash_bwd)


def _maybe_shard_map(fn, arg_specs, out_spec):
    """Wrap a grouped-kernel call in shard_map when an ambient mesh is set —
    GSPMD otherwise REPLICATES pallas_call operands (models/runtime.py)."""
    from jax.sharding import PartitionSpec as P

    from repro.models import runtime

    ctx = runtime.current()
    if ctx is None:
        return fn
    mesh, _ = ctx
    return jax.shard_map(fn, mesh=mesh, in_specs=arg_specs,
                         out_specs=out_spec, check_vma=False)


def flash_attention(q, k, v, *, causal=True, window=None, prefix=0,
                    bq=512, bk=512):
    """Differentiable flash attention, (B, S, H, D) layout (GQA via the KV
    dim of k/v).  Block sizes auto-shrink to divide the sequence lengths.
    Runs per-shard (shard_map over the fused batch*kv dim) when an ambient
    mesh is active."""
    from jax.sharding import PartitionSpec as P

    from repro.kernels import flash_attention as FA
    from repro.models import runtime

    B, KV = q.shape[0], k.shape[2]
    bq = _fit_block(q.shape[1], bq)
    bk = _fit_block(k.shape[1], bk)
    qg, kg, vg = FA.group(q, k, v)
    ctx = runtime.current()
    if ctx is not None:
        bkv = runtime.fused_bkv_spec()
        spec4 = P(bkv, None, None, None)
        spec3 = P(bkv, None, None)
        call = _maybe_shard_map(
            lambda a, b_, c: _flash_grouped(a, b_, c, causal, window, prefix,
                                            bq, bk),
            (spec4, spec3, spec3), spec4)
        out = call(qg, kg, vg)
    else:
        out = _flash_grouped(qg, kg, vg, causal, window, prefix, bq, bk)
    return FA.ungroup(out, B, KV)
