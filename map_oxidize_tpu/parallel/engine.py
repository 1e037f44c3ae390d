"""Sharded streaming reduce engine: the multi-chip twin of
:class:`map_oxidize_tpu.runtime.engine.DeviceReduceEngine`.

Where the reference funnels every reduce into one mutex-guarded HashMap
(``/root/reference/src/main.rs:113,131-134``), this engine keeps one
accumulator *per shard*, each owning a hash-partition of the key space;
batches are routed to their owners by the ``all_to_all`` exchange in
:mod:`map_oxidize_tpu.parallel.shuffle` and folded locally.  The host sees
the same ``feed(MapOutput)`` / ``finalize()`` / ``top_k(k)`` surface
(:class:`~map_oxidize_tpu.runtime.engine.StreamingEngineBase`), so the driver
is engine-agnostic — swapping 1 chip for a v4-pod slice is a config change.

Host->device feeding uses global row-major arrays sharded on dim 0
(``NamedSharding(mesh, P('shards'))``): ``jax.device_put`` splits the batch
across chips, which doubles as the *map-side* data parallelism — each shard
"maps" (receives) B/S of the rows, then the exchange re-partitions by key.
"""

from __future__ import annotations

from functools import partial

import numpy as np
import jax
import jax.numpy as jnp

from map_oxidize_tpu.api import Reducer
from map_oxidize_tpu.config import JobConfig
from map_oxidize_tpu.ops.segment_reduce import make_accumulator
from map_oxidize_tpu.parallel.mesh import SHARD_AXIS, make_mesh, sharded
from map_oxidize_tpu.parallel.shuffle import build_sharded_ops, mesh_program
from map_oxidize_tpu.runtime.engine import StreamingEngineBase
from map_oxidize_tpu.utils.logging import get_logger

_log = get_logger(__name__)


class ShuffleOverflowError(RuntimeError):
    """A hash bucket exceeded the exchange-buffer capacity; rows would have
    been dropped.  Increase ``bucket_cap`` (or shrink the batch)."""


def _build_init(mesh, capacity: int, value_shape: tuple, value_dtype,
                combine: str):
    """The empty accumulator of ``capacity`` rows a shard, as a jitted
    fill with out_shardings: it materializes directly on the mesh (no host
    buffer over the slow link) and never touches the default device — the
    mesh may be virtual CPUs while a sick TPU is default.  Closes over
    values only, never an engine, since the program outlives the job."""
    from map_oxidize_tpu.obs.compile import observed_jit

    S = mesh.shape[SHARD_AXIS]
    return observed_jit("shuffle/init_acc", jax.jit(
        lambda: make_accumulator(capacity * S, value_shape, value_dtype,
                                 combine, xp=jnp),
        out_shardings=sharded(mesh),
    ), tag=(capacity, S, str(value_dtype)))


class ShardedReduceEngine(StreamingEngineBase):
    """Folds MapOutputs into per-shard accumulators over a device mesh."""

    def __init__(
        self,
        config: JobConfig,
        reducer: Reducer,
        value_shape: tuple = (),
        value_dtype=np.int32,
        mesh=None,
        bucket_cap: int = 0,
        overflow_check_every: int = 16,
        exchange_method: str = "all_to_all",
    ):
        super().__init__(config, reducer, value_shape, value_dtype,
                         overflow_check_every)
        #: wire program for the shuffle exchange — the chooser's knob
        #: (parallel.shuffle.choose_collective), resolved by the driver
        self.exchange_method = exchange_method
        self.mesh = mesh if mesh is not None else make_mesh(
            config.num_shards, config.backend
        )
        self.S = self.mesh.shape[SHARD_AXIS]
        # per-shard sizes; global arrays are S x these
        self.batch_per_shard = max(1, config.batch_size // self.S)
        self.max_capacity = max(1, config.key_capacity // self.S)
        self.capacity = min(
            max(1, -(-config.initial_key_capacity // self.S)),
            self.max_capacity,
        )
        self.feed_batch = self.batch_per_shard * self.S
        self._sharding = sharded(self.mesh)

        self._merge, self._topk, self._grow, self.bucket_cap = build_sharded_ops(
            self.mesh, self.combine, bucket_cap, self.batch_per_shard,
            exchange_method=exchange_method,
        )
        init = mesh_program(
            ("init_acc", self.mesh, self.capacity, self.value_shape,
             self.value_dtype, self.combine),
            partial(_build_init, self.mesh, self.capacity, self.value_shape,
                    self.value_dtype, self.combine))
        self._acc = list(init())
        # [S] cumulative dropped-row counter (exchange-bucket drops plus
        # accumulator truncation), threaded through every merge
        self._overflow = jax.device_put(
            np.zeros(self.S, np.int32), self._sharding
        )

    def _round_batch(self, n: int) -> int:
        b = super()._round_batch(n)
        return -(-b // self.S) * self.S  # shard_map needs S | batch rows

    def _incoming(self, batch_rows: int) -> int:
        # worst-case rows landing on one shard in this merge: every source
        # shard can fill its bucket for us, but never more than it holds
        return min(batch_rows, self.S * self.bucket_cap)

    def _read_live(self) -> int:
        return int(np.max(np.asarray(self._n_unique)))  # worst shard

    def _apply_grow(self, new_cap: int) -> None:
        self._acc = list(self._grow(*self._acc, new_cap - self.capacity))

    def _put(self, padded):
        return jax.device_put(padded, self._sharding)

    def _merge_batch(self, batch) -> None:
        self.feed_device(*batch, count_rows=False)

    def feed_device(self, hi, lo, vals, count_rows: bool = True) -> None:
        """Merge a device-resident batch already sharded over the mesh (row
        count divisible by S) — the hand-off used by the sharded on-device
        map path: tokenized rows flow from the shard_map tokenizer straight
        into the all_to_all exchange with no host round trip."""
        if hi.shape[0] % self.S:
            raise ValueError(
                f"sharded feed_device needs S|rows; got {hi.shape[0]} rows "
                f"for {self.S} shards")
        incoming = self._incoming(hi.shape[0])
        self._ensure_capacity(incoming)
        if count_rows:
            self.rows_fed += hi.shape[0]
        import time as _time

        t0 = _time.perf_counter()
        *self._acc, self._n_unique, self._overflow = self._merge(
            *self._acc, self._overflow, hi, lo, vals
        )
        self._n_live_ub += incoming
        if self.obs is not None:
            from map_oxidize_tpu.parallel.shuffle import (
                exchange_payload_bytes,
            )

            reg = self.obs.registry
            reg.count("shuffle/exchanges")
            reg.count("shuffle/rows_exchanged", hi.shape[0])
            payload = exchange_payload_bytes(
                self.S, self.bucket_cap,
                int(self.value_dtype.itemsize
                    * max(1, int(np.prod(self.value_shape, dtype=np.int64)))
                    ))
            # method-agnostic logical-exchange accounting identity (the
            # merge report and gates read this name regardless of which
            # wire program the chooser picked)
            reg.count("shuffle/all_to_all_bytes", payload)
            reg.set("shuffle/exchange_collective", self.exchange_method)
            # the per-merge psum payloads: the [S] unique counts + the [S]
            # overflow counter, int32 each, replicated over S shards
            psum_payload = 2 * 4 * self.S * self.S
            reg.count("shuffle/psum_bytes", psum_payload)
            from map_oxidize_tpu.obs.metrics import sample_collective_wall

            lat_ms = sample_collective_wall(self, "_exchanges", t0,
                                            self._overflow)
            reg.comm(self.exchange_method, "shuffle/merge", payload,
                     shape=(self.S, self.bucket_cap), latency_ms=lat_ms)
            reg.comm("psum", "shuffle/merge", psum_payload,
                     shape=(self.S,))

    def export_state(self) -> dict:
        """Host snapshot of the sharded reduce state (see the single-device
        twin); arrays are fetched global, restored re-sharded."""
        return {
            "acc_hi": np.asarray(self._acc[0]),
            "acc_lo": np.asarray(self._acc[1]),
            "acc_vals": np.asarray(self._acc[2]),
            "ovf": np.asarray(self._overflow),
            "n_unique": (np.asarray(self._n_unique)
                         if self._n_unique is not None
                         else np.full(self.S, -1, np.int32)),
            "n_live_ub": np.int64(self._n_live_ub),
            "rows_fed": np.int64(self.rows_fed),
        }

    def import_state(self, st: dict) -> None:
        self.capacity = int(st["acc_hi"].shape[0]) // self.S
        self._acc = [jax.device_put(np.asarray(st[k]), self._sharding)
                     for k in ("acc_hi", "acc_lo", "acc_vals")]
        self._overflow = jax.device_put(
            np.asarray(st["ovf"], np.int32), self._sharding)
        n = np.asarray(st["n_unique"], np.int32)
        self._n_unique = None if int(n[0]) < 0 else n
        self._n_live_ub = int(st["n_live_ub"])
        self.rows_fed = int(st["rows_fed"])

    def _check_health(self) -> None:
        dropped = int(np.asarray(self._overflow)[0])  # host sync
        if dropped:
            raise ShuffleOverflowError(
                f"{dropped} rows dropped (bucket overflow or a shard "
                f"accumulator past key_capacity); increase bucket_cap / "
                "key_capacity"
            )

    def _finalize(self):
        self._check_health()
        if self._n_unique is None:
            return (*self._acc, 0)
        return (*self._acc, int(np.sum(np.asarray(self._n_unique))))

    def _top_k_device(self, k: int):
        out = self._topk(*self._acc, k)
        if self.obs is not None:
            # two-level top-k moves S*k_local candidate rows per shard
            # over the all_gather (hi+lo planes plus the value column)
            k_local = min(k, self.capacity)
            vbytes = int(self.value_dtype.itemsize * max(
                1, int(np.prod(self.value_shape, dtype=np.int64))))
            self.obs.registry.comm(
                "all_gather", "shuffle/top_k",
                self.S * self.S * k_local * (8 + vbytes),
                shape=(self.S, k_local))
        return out
