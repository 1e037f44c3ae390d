"""Hash-bucket ``all_to_all`` shuffle + sharded segment-reduce.

This is the TPU-native replacement for the reference's shuffle, which does not
exist: every reduce worker merges into ONE global ``HashMap`` under ONE mutex
(``/root/reference/src/main.rs:111-150``, lock at 131), so its reduce is
serialized and its key space is never partitioned.  On a mesh the idiomatic
formulation is owner-computes over a hash partition of the key space:

    per shard: bucket rows by ``hash % num_shards``  ->  sort by bucket  ->
    scatter into a fixed [S, cap] send buffer  ->  ``lax.all_to_all`` over
    ICI  ->  every row now sits on its owner shard  ->  local sort +
    segment-combine into that shard's accumulator.

Ragged bucket sizes (SURVEY.md §7 hard part (b)) are handled by
pad-to-capacity: the send buffer gives every destination shard ``cap`` slots,
padding carries SENTINEL keys, and per-bucket overflow is *counted* (psum over
shards) and returned so the host can raise instead of silently dropping rows.
With a healthy hash, bucket loads concentrate near B/S, so ``cap ~ 2B/S`` is
ample slack; the engine exposes the knob.

Global top-k is two-level: per-shard ``lax.top_k`` over the local accumulator,
``all_gather`` of the S*k candidates (k rows per shard cross ICI, not the
whole key space), final ``top_k`` replicated.  This replaces the reference's
full host-side sort of every distinct word (main.rs:184-192).

Everything here is shape-static and compiles to one XLA program per
(batch, capacity, k) config; collectives are XLA's own ICI/DCN lowering —
no NCCL/MPI analog exists or is needed.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from map_oxidize_tpu.ops.hashing import SENTINEL
from map_oxidize_tpu.ops.segment_reduce import reduce_pairs
from map_oxidize_tpu.parallel.mesh import SHARD_AXIS
from map_oxidize_tpu.utils.jax_compat import shard_map


#: the exchange programs the chooser can route through: the monolithic
#: ``all_to_all`` and its portable decomposition (arXiv:2112.01075) —
#: ``all_gather`` every shard's send buffer, then dynamic-slice the
#: block addressed to this shard.  Same routed rows bit for bit; which
#: one is faster depends on (payload bucket, topology), which is what
#: the calibration store measures.
EXCHANGE_COLLECTIVES = ("all_to_all", "all_gather")


def exchange_payload_bytes(num_shards: int, bucket_cap: int,
                           value_row_bytes: int) -> int:
    """Bytes one full exchange moves over ICI/DCN: every shard sends a
    ``[S, cap]`` buffer of (hi, lo, value) planes, so the global payload
    is ``S * S * cap`` rows of ``8 + value_row_bytes`` each.  A host-side
    accounting identity for the metrics registry — the collective itself
    is inside XLA and can't self-report.  The SAME identity prices both
    exchange methods (the all_gather decomposition moves more raw bytes,
    but its measured latency curve is keyed on the logical exchange
    payload so the chooser compares like with like)."""
    return num_shards * num_shards * bucket_cap * (8 + value_row_bytes)


def choose_collective(store, ident: dict, num_shards: int,
                      bucket_cap: int, value_row_bytes: int = 8,
                      min_samples: int | None = None,
                      requested: str = "auto") -> dict:
    """The store-driven exchange-collective decision (ROADMAP item 2's
    "auto-selected from the calibration store rather than hard-coded").

    Prices one full exchange at this job's measured payload bucket under
    both :data:`EXCHANGE_COLLECTIVES` curves and picks the cheaper —
    but ONLY when the store's evidence is trustworthy: an exact-bucket
    curve with at least ``min_samples`` sampled latencies for BOTH
    methods.  Anything less falls back to the hard-coded default with a
    NAMED reason (``provenance: default``) — a cold store, a bucket the
    curves only cover by extrapolation, or thin evidence must never
    silently steer the exchange.  ``requested != "auto"`` short-circuits
    as a user pin (``provenance: pinned``).

    Returns the decision document the plan doc / ledger / ``/status``
    carry verbatim: ``{method, provenance, reason, bucket,
    payload_bytes, evidence: {collective: {predicted_ms, samples,
    by_source, bucket_distance}}}``."""
    from map_oxidize_tpu.obs.calib import (
        CALIB_MIN_SAMPLES,
        collective_evidence,
        interpolate_latency_ms,
        shape_bucket,
    )

    if min_samples is None:
        min_samples = CALIB_MIN_SAMPLES
    payload = exchange_payload_bytes(num_shards, bucket_cap,
                                     value_row_bytes)
    bucket = shape_bucket(payload)
    default = EXCHANGE_COLLECTIVES[0]
    decision: dict = {"bucket": bucket, "payload_bytes": int(payload)}
    if requested != "auto":
        decision.update(method=requested, provenance="pinned",
                        reason=f"user pinned {requested}",
                        evidence={"requested": requested})
        return decision
    evidence: dict = {}
    fallback_reason = None
    for coll in EXCHANGE_COLLECTIVES:
        ev = collective_evidence(store, ident, coll, bucket)
        lat = interpolate_latency_ms(store, ident, coll, payload)
        evidence[coll] = {
            "predicted_ms": None if lat is None else round(lat, 4),
            "samples": ev["samples"], "by_source": ev["by_source"],
            "bucket_distance": ev["bucket_distance"],
        }
        if fallback_reason is not None:
            continue
        if lat is None or ev["bucket_distance"] is None:
            fallback_reason = (f"cold store: no sampled {coll} curve "
                               f"under this identity")
        elif ev["bucket_distance"] > 0:
            fallback_reason = (
                f"out of bucket range: nearest sampled {coll} bucket is "
                f"{ev['bucket_distance']} pow2 step(s) from {bucket} "
                "(extrapolation, not evidence)")
        elif ev["samples"] < min_samples:
            fallback_reason = (
                f"below min-samples floor: {coll}@{bucket} has "
                f"{ev['samples']} sampled latencies < {min_samples}")
    decision["evidence"] = evidence
    if fallback_reason is not None:
        decision.update(method=default, provenance="default",
                        reason=fallback_reason)
        return decision
    best = min(EXCHANGE_COLLECTIVES,
               key=lambda c: evidence[c]["predicted_ms"])
    decision.update(
        method=best, provenance="curve",
        reason=(f"store curve @ {bucket}: "
                + " vs ".join(f"{c} {evidence[c]['predicted_ms']}ms"
                              for c in EXCHANGE_COLLECTIVES)))
    return decision


def bucket_of(hi: jnp.ndarray, lo: jnp.ndarray, num_shards: int) -> jnp.ndarray:
    """Owner shard of a 64-bit key.  Mixes both planes (FNV-1a's low bits
    alone are its weakest) and must match any host-side partitioner —
    :func:`map_oxidize_tpu.obs.dataplane.partition_of` is the numpy twin
    the data-plane audit buckets by (a parity test pins the two), so the
    audit's per-partition rows ARE this exchange's routing histogram."""
    return ((hi ^ lo) % jnp.uint32(num_shards)).astype(jnp.int32)


def range_dest(hi, lo, sp_hi, sp_lo) -> jnp.ndarray:
    """Owner shard of a 64-bit key under a RANGE partition (the total-order
    sort's routing): the count of splitters ``<=`` the key — exactly
    ``searchsorted(splitters, key, side='right')``, so a key equal to
    splitter ``j`` lands deterministically on shard ``j+1`` and shard 0
    owns everything below the first splitter.  Keys travel as (hi, lo)
    u32 planes (x64 is disabled in-trace), so the comparison is the
    lexicographic plane compare; splitters are S-1 values broadcast
    against the batch.  MUST match the host partitioner
    (:func:`map_oxidize_tpu.workloads.sort.range_partition`) bit for bit —
    the property suite pins the pair."""
    ge = (hi[:, None] > sp_hi[None, :]) | (
        (hi[:, None] == sp_hi[None, :]) & (lo[:, None] >= sp_lo[None, :]))
    return jnp.sum(ge.astype(jnp.int32), axis=1)


def _exchange(hi, lo, vals, num_shards: int, cap: int, dest=None,
              method: str = "all_to_all"):
    """Per-shard body: route rows to their owner shard.

    ``dest`` overrides the hash-bucket destination per row (the sort
    engine's range partition); padding rows are re-routed round-robin
    either way.  ``method`` picks the wire program
    (:data:`EXCHANGE_COLLECTIVES`): the monolithic ``all_to_all``, or
    the decomposed ``all_gather`` + dynamic-slice resharding — identical
    routed rows by construction (the slice extracts exactly the block
    ``all_to_all`` would have delivered), so the chooser can flip
    methods without touching results.  Returns ``(hi, lo, vals)`` of
    shape ``[S*cap, ...]`` — the rows this shard owns after the exchange
    — plus the global count of overflow-dropped rows (replicated scalar;
    caller raises on nonzero).
    """
    if method not in EXCHANGE_COLLECTIVES:
        raise ValueError(f"exchange method must be one of "
                         f"{EXCHANGE_COLLECTIVES}, got {method!r}")
    B = hi.shape[0]
    S = num_shards
    is_pad = (hi == jnp.uint32(SENTINEL)) & (lo == jnp.uint32(SENTINEL))
    # padding rows are spread round-robin so they never overflow one bucket
    rr = (jnp.arange(B, dtype=jnp.int32) % S)
    if dest is None:
        dest = bucket_of(hi, lo, S)
    dest = jnp.where(is_pad, rr, dest)

    # stable sort by destination; values ride as a permutation index
    idx = jnp.arange(B, dtype=jnp.int32)
    dest_s, perm = lax.sort((dest, idx), num_keys=1, is_stable=True)
    hi_s = jnp.take(hi, perm)
    lo_s = jnp.take(lo, perm)
    vals_s = jnp.take(vals, perm, axis=0)

    counts = jnp.bincount(dest, length=S)
    starts = jnp.cumsum(counts) - counts
    rank = idx - jnp.take(starts, dest_s)  # position within the bucket
    # overflow counts only REAL rows against cap: the pre-combine compacts
    # real rows ahead of the padding tail, so within each bucket (stable sort
    # by dest) real rows occupy the lowest ranks and any dropped tail is
    # padding unless the bucket's *real* count exceeds cap.  Counting pads
    # too would abort correct runs whose dropped tail was padding only.
    real_counts = jnp.bincount(jnp.where(is_pad, S, dest), length=S)
    overflow = jnp.sum(jnp.maximum(real_counts - cap, 0))

    # scatter into the [S, cap] send buffer; rank >= cap rows are dropped
    # (mode='drop') and accounted for by `overflow`
    buf_hi = jnp.full((S, cap), SENTINEL, jnp.uint32)
    buf_lo = jnp.full((S, cap), SENTINEL, jnp.uint32)
    buf_vals = jnp.zeros((S, cap) + vals.shape[1:], vals.dtype)
    buf_hi = buf_hi.at[dest_s, rank].set(hi_s, mode="drop")
    buf_lo = buf_lo.at[dest_s, rank].set(lo_s, mode="drop")
    buf_vals = buf_vals.at[dest_s, rank].set(vals_s, mode="drop")

    if method == "all_gather":
        # decomposed resharding: gather every shard's [S, cap] send
        # buffer ([S_src, S, cap]) and dynamic-slice column `my` —
        # g[i, my] is exactly the block shard i addressed to this shard,
        # i.e. the row block all_to_all would have delivered
        my = lax.axis_index(SHARD_AXIS)

        def _reshard(buf):
            g = lax.all_gather(buf, SHARD_AXIS)
            return lax.dynamic_index_in_dim(g, my, axis=1,
                                            keepdims=False)

        ex_hi = _reshard(buf_hi)
        ex_lo = _reshard(buf_lo)
        ex_vals = _reshard(buf_vals)
    else:
        # ICI exchange: row block [d, :] goes to shard d; received block
        # i came from shard i.  tiled=True keeps the [S, cap] shape.
        ex_hi = lax.all_to_all(buf_hi, SHARD_AXIS, 0, 0, tiled=True)
        ex_lo = lax.all_to_all(buf_lo, SHARD_AXIS, 0, 0, tiled=True)
        ex_vals = lax.all_to_all(buf_vals, SHARD_AXIS, 0, 0, tiled=True)

    total_overflow = lax.psum(overflow, SHARD_AXIS)
    flat = (S * cap,)
    return (
        ex_hi.reshape(flat),
        ex_lo.reshape(flat),
        ex_vals.reshape(flat + vals.shape[1:]),
        total_overflow,
    )


def _merge_step(acc_hi, acc_lo, acc_vals, ovf_in, b_hi, b_lo, b_vals,
                num_shards: int, cap: int, combine: str,
                method: str = "all_to_all"):
    """Per-shard body of one streaming fold: pre-combine the local batch,
    shuffle it, then sort+segment-combine into this shard's accumulator.
    ``ovf_in`` is the running overflow counter — carried through the step so
    no merge's drops can be shadowed by a later clean merge."""
    C = acc_hi.shape[0]
    # Local pre-combine (a device-side "combiner"): collapses duplicate keys
    # before the exchange, so per-bucket load scales with the batch's
    # *distinct* keys, not its token multiplicity — a Zipf-skewed batch would
    # otherwise concentrate one hot key's duplicates into one bucket and
    # overflow cap.  Also shrinks ICI bytes by the duplication factor, and the
    # sort it costs was going to be paid post-exchange anyway.
    b_hi, b_lo, b_vals, _ = reduce_pairs(b_hi, b_lo, b_vals, combine)
    r_hi, r_lo, r_vals, overflow = _exchange(b_hi, b_lo, b_vals,
                                             num_shards, cap,
                                             method=method)
    hi = jnp.concatenate([acc_hi, r_hi])
    lo = jnp.concatenate([acc_lo, r_lo])
    vals = jnp.concatenate([acc_vals, r_vals])
    u_hi, u_lo, u_vals, n_unique = reduce_pairs(hi, lo, vals, combine)
    # cumulative dropped-row counter: exchange-bucket drops (replicated psum)
    # plus this shard's accumulator truncation (psum'd so the counter stays
    # identical on every shard and the out_spec uniform)
    acc_drop = lax.psum(jnp.maximum(n_unique - C, 0), SHARD_AXIS)
    return (
        u_hi[:C],
        u_lo[:C],
        u_vals[:C],
        n_unique.reshape(1),            # per-shard unique count -> [S] global
        ovf_in + overflow.reshape(1) + acc_drop.reshape(1),
    )


def _topk_step(acc_hi, acc_lo, acc_vals, k_local: int, k_final: int):
    """Per-shard body: local candidates -> all_gather -> global top-k.

    ``k_local = min(k, per-shard capacity)`` is *complete*: a shard holds at
    most capacity distinct keys, so when k exceeds capacity its whole
    accumulator is its candidate set and nothing can be missed.  The final
    top-k runs over all ``S * k_local`` gathered candidates and returns
    ``k_final = min(k, S * k_local)`` rows.  Any monoid is eligible:
    padding rows are masked to the dtype floor (ops.topk.mask_padding)
    rather than trusted to carry a losing identity — a min identity is the
    dtype MAX and would otherwise win."""
    from map_oxidize_tpu.ops.topk import mask_padding

    v, i = lax.top_k(mask_padding(acc_hi, acc_lo, acc_vals), k_local)
    h = jnp.take(acc_hi, i)
    l = jnp.take(acc_lo, i)
    gh = lax.all_gather(h, SHARD_AXIS, tiled=True)   # [S*k_local]
    gl = lax.all_gather(l, SHARD_AXIS, tiled=True)
    gv = lax.all_gather(v, SHARD_AXIS, tiled=True)
    # final select: value-descending with LIVE rows preferred on ties.  A
    # plain top_k would prefer the lowest gathered index, and a lower
    # shard's floor-masked padding precedes a higher shard's real
    # floor-valued key in the gather — lexsort (value asc, live last) then
    # take the tail reversed, so among equal values live rows win.
    live = (~((gh == jnp.uint32(SENTINEL))
              & (gl == jnp.uint32(SENTINEL)))).astype(jnp.int32)
    order = jnp.lexsort((live, gv))
    sel = order[-k_final:][::-1]
    return jnp.take(gh, sel), jnp.take(gl, sel), jnp.take(gv, sel)


#: the mesh programs this process has built, by what defines each (see
#: :func:`mesh_program`): every engine makes a new ``Mesh`` and closures
#: of its own, so without this each job traced, lowered and compiled
#: (or cache-loaded) its merge, growth chain and init again
_PROGRAMS: dict = {}


def mesh_program(key: tuple, build):
    """The program under ``key``, made by ``build()`` the first time this
    process asks for it and the same ``jax.jit`` object after that, so a
    later job's calls hit programs that are already compiled — what the
    one-chip engine's module-level jits give it.  ``key`` holds
    everything the program closes over: its kind, the ``Mesh`` (meshes
    over the same devices and axis compare and hash equal) and its
    static parameters.  Argument shapes and dtypes need not be in it:
    they are the jit's own cache key.  Counts ``shuffle/programs_built``
    or ``shuffle/programs_reused`` into the current job's metrics."""
    from map_oxidize_tpu.obs.context import current_obs

    fn = _PROGRAMS.get(key)
    counter = "shuffle/programs_reused"
    if fn is None:
        fn = _PROGRAMS.setdefault(key, build())
        counter = "shuffle/programs_built"
    obs = current_obs()
    if obs is not None:
        obs.registry.count(counter)
    return fn


def _build_merge(mesh, combine: str, bucket_cap: int, exchange_method: str):
    from map_oxidize_tpu.obs.compile import observed_jit

    S = mesh.shape[SHARD_AXIS]
    spec = P(SHARD_AXIS)
    merge = shard_map(
        partial(_merge_step, num_shards=S, cap=bucket_cap,
                combine=combine, method=exchange_method),
        mesh=mesh,
        in_specs=(spec,) * 7,
        out_specs=(spec, spec, spec, spec, spec),
    )
    # the exchange method is part of the program identity: a chooser
    # flip IS a new XLA program, and the compile ledger must see it as
    # one (not a mystery recompile of the same name)
    return observed_jit("shuffle/merge",
                        jax.jit(merge, donate_argnums=(0, 1, 2, 3)),
                        tag=exchange_method)


def _build_topk(mesh, k_local: int, k_final: int):
    from map_oxidize_tpu.obs.compile import observed_jit

    spec = P(SHARD_AXIS)
    # check_vma=False: the result of top_k over an all_gather IS
    # replicated, but shard_map's static replication checker can't prove
    # it through the take/top_k composition.
    f = shard_map(
        partial(_topk_step, k_local=k_local, k_final=k_final),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=(P(), P(), P()),
        check_vma=False,
    )
    return observed_jit("shuffle/top_k", jax.jit(f), tag=(k_local, k_final))


def _build_grow(mesh, combine: str, pad_per_shard: int):
    """Grow each shard's accumulator by ``pad_per_shard`` SENTINEL rows.
    Growth is per-shard concatenation — a global concat would append all
    padding after shard S-1's block instead of after each shard's."""
    from map_oxidize_tpu.obs.compile import observed_jit
    from map_oxidize_tpu.ops.segment_reduce import make_accumulator

    def _grow(h, l, v):
        # xp=jnp: this runs inside the jit trace, where the fill must
        # compile to an on-device broadcast, not a pad-sized constant
        p_h, p_l, p_v = make_accumulator(
            pad_per_shard, v.shape[1:], v.dtype, combine, xp=jnp
        )
        return (
            jnp.concatenate([h, p_h]),
            jnp.concatenate([l, p_l]),
            jnp.concatenate([v, p_v]),
        )

    spec = P(SHARD_AXIS)
    f = shard_map(_grow, mesh=mesh, in_specs=(spec,) * 3,
                  out_specs=(spec,) * 3)
    # each growth step IS a new program (new accumulator shape), which
    # the compile ledger records under one name — a job's first
    # capacity-growth chain shows up as shuffle/grow compiles with cause
    # new_input_shape; later jobs on the mesh reuse the chain
    return observed_jit("shuffle/grow", jax.jit(f, donate_argnums=(0, 1, 2)))


def build_sharded_ops(mesh, combine: str = "sum", bucket_cap: int = 0,
                      batch_per_shard: int = 0,
                      exchange_method: str = "all_to_all"):
    """The sharded merge step, top-k and growth for ``mesh``, each built
    once per process (:func:`mesh_program`).

    Returns ``(merge_fn, topk_fn, grow_fn, bucket_cap)``:

    * ``merge_fn(acc_hi, acc_lo, acc_vals, ovf, b_hi, b_lo, b_vals)`` — all
      args global row-major arrays sharded on dim 0; returns updated
      accumulator triple (donated, stays in HBM), per-shard unique counts
      ``[S]`` and the cumulative overflow counter ``[S]`` (all entries equal;
      nonzero = rows were dropped, caller must raise).
    * ``topk_fn(acc_hi, acc_lo, acc_vals, k)`` — replicated
      ``(hi_k, lo_k, vals_k)``.
    * ``grow_fn(acc_hi, acc_lo, acc_vals, pad_per_shard)`` — the
      accumulator with ``pad_per_shard`` SENTINEL rows added to each
      shard's block (donated).

    ``bucket_cap`` = slots per destination shard in the exchange buffer.  0
    derives ``2*ceil(B/S) + 16``: expected load is B/S, doubled for hash
    variance, plus slack for the round-robin padding rows (at most
    ``ceil(B/S)`` per bucket) on short batches.
    """
    S = mesh.shape[SHARD_AXIS]
    if bucket_cap <= 0:
        if batch_per_shard <= 0:
            raise ValueError("need bucket_cap or batch_per_shard")
        bucket_cap = min(batch_per_shard, 2 * (-(-batch_per_shard // S)) + 16)

    merge = mesh_program(
        ("merge", mesh, combine, bucket_cap, exchange_method),
        partial(_build_merge, mesh, combine, bucket_cap, exchange_method))

    def grow_fn(acc_hi, acc_lo, acc_vals, pad_per_shard: int):
        grow = mesh_program(("grow", mesh, combine, pad_per_shard),
                            partial(_build_grow, mesh, combine,
                                    pad_per_shard))
        return grow(acc_hi, acc_lo, acc_vals)

    def topk_fn(acc_hi, acc_lo, acc_vals, k: int):
        cap_per_shard = acc_hi.shape[0] // S
        k_local = min(k, cap_per_shard)
        k_final = min(k, S * k_local)
        topk = mesh_program(("top_k", mesh, k_local, k_final),
                            partial(_build_topk, mesh, k_local, k_final))
        return topk(acc_hi, acc_lo, acc_vals)

    return merge, topk_fn, grow_fn, bucket_cap
