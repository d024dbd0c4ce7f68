"""Mesh-sharded fit/compress: DP trainer programs, a species/block-row
sharded guarantee engine, and the streaming sharded ingest buffer.

The paper's production fields (full species sets, hundreds of timesteps)
exceed a single accelerator's memory, so the fit/compress path gains a
``("data",)`` mesh dimension in three places:

* **Data-parallel trainer** — :func:`dp_scan_program` builds the
  ``MiniBatchTrainer`` mesh mode: one ``jit(shard_map(lax.scan(...)))``
  program with batch-row-sharded device data, psum'd gradients, and
  donated ``(params, opt_state)`` carries. Every shard draws its local
  mini-batch through the *same* :func:`~repro.train.train_loop.
  batch_indices` law (shard ``i`` folds its axis index into the batch
  key), so on a 1-device mesh the traced program is op-for-op the
  existing scan program and the loss trajectory and final params are
  **bit-identical** to the single-device trainer — the gate tier-1
  asserts. On ``P > 1`` devices the trajectory is the valid DP-SGD one
  (global batch = P local batches, gradients exchanged as
  ``psum / P``), which reduction order makes close to but not bitwise
  the 1-device run. The gradient exchange optionally routes through
  :func:`repro.parallel.gradient_compression.quantized_psum` (int8
  payload + fp32 block scales on the wire); :func:`dp_wire_report`
  accounts the per-step wire bytes either way from the static leaf
  shapes.

* **Sharded guarantee engine** — :class:`ShardedGuaranteeEngine`
  overrides the :class:`~repro.core.gae.GuaranteeEngine` dispatch seam:
  each batched Pallas dispatch (projection, masked select-accumulate,
  correction replay) is split into contiguous per-shard programs over
  the species axis (and over block rows when shards outnumber species),
  placed one per device, and the fetched results concatenated. The
  kernels are per-species and per-block-row pure, so the concatenated
  CSR artifacts — and therefore the serialized container — are
  **byte-identical** to the single-device engine's (asserted in tier-1
  and by ``chip_smoke.py --four-chips``). Prepared tensors stay on host and
  are chunk-staged per dispatch, so no single device ever holds the
  full (S, NB, D) problem.

* **Streaming sharded ingest** — :class:`ShardedBlockStore` is the
  mesh-aware ``fit_stream`` landing buffer: each two-pass ingest chunk
  is normalized and blocked on host (one chunk at a time) and written
  straight into a row-sharded device array via a donated
  ``dynamic_update_slice`` program. The full normalized field is never
  materialized on host, and each device holds only ``NB / P`` block
  rows — a field larger than one device's memory fits. (The *compress*
  stage still builds host-numpy mirrors for the guarantee math — the
  out-of-core constraint this buffer removes is device memory, and the
  ingest/fit host peak, which the allocation-tracking test pins to one
  chunk.)

CPU CI validates everything on a forced host mesh
(``XLA_FLAGS=--xla_force_host_platform_device_count=8``; tier-1 honors
``REPRO_HOST_DEVICES`` via the root conftest). The structural wins —
one program per shard, no host gathers mid-fit, donated carries — are
the accelerator-dominant terms, same argument as the compiled trainer.

Demo: ``python -m repro.parallel.mesh_fit`` (run with forced host
devices) prints the per-device ingest memory high-water against the
single-device total; quickstart step 9 drives it.
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import gae

#: the one mesh axis this module shards over (batch rows / species rows)
DATA_AXIS = "data"


# ---------------------------------------------------------------------------
# mesh plumbing
# ---------------------------------------------------------------------------
def host_mesh(n_devices: Optional[int] = None) -> Mesh:
    """A 1-D ``("data",)`` mesh over the first ``n_devices`` devices
    (default: all). On CPU CI the device count comes from
    ``--xla_force_host_platform_device_count``."""
    devs = jax.devices()
    n = len(devs) if n_devices is None else int(n_devices)
    if not 1 <= n <= len(devs):
        raise ValueError(
            f"host_mesh wants {n} devices but {len(devs)} are available"
        )
    return Mesh(np.array(devs[:n]), (DATA_AXIS,))


def mesh_size(mesh: Mesh) -> int:
    return int(np.prod(mesh.devices.shape))


def data_sharding(mesh: Mesh) -> NamedSharding:
    """Rows (leading axis) split over the data axis."""
    return NamedSharding(mesh, P(DATA_AXIS))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def mesh_cache_key(mesh: Mesh) -> tuple:
    """Hashable identity for program caches: the device ids, in order."""
    return tuple(int(d.id) for d in mesh.devices.flat)


def shard_rows(array, mesh: Mesh):
    """Place an array row-sharded over the mesh (no-op if already so)."""
    return jax.device_put(jnp.asarray(array), data_sharding(mesh))


# ---------------------------------------------------------------------------
# (1) data-parallel MiniBatchTrainer program
# ---------------------------------------------------------------------------
def dp_scan_program(trainer, steps: int, n: int, bs: int, log_every: int,
                    mesh: Mesh, quantized: bool, n_data: int = 1):
    """Build the trainer's mesh program: ``jit(shard_map(scan(step)))``.

    ``n`` and ``bs`` are the *global* row/batch counts, both divisible by
    the mesh size (the trainer trims). Each shard samples its local batch
    via ``batch_indices`` over its own ``n/P`` rows under a per-shard key
    (``fold_in(bkey, axis_index)``); gradients are exchanged as
    ``psum/P`` (== the global-batch mean for equal shards) and the logged
    loss is the pmean. All branches on the mesh size are *trace-time*:
    the 1-device program contains no collectives and is op-for-op the
    single-device scan program — that is what makes the P=1 bit-identity
    gate hold by construction rather than by luck.
    """
    from repro.parallel import gradient_compression as gc
    from repro.train import optimizer as opt
    from repro.train.train_loop import batch_indices

    n_p = mesh_size(mesh)
    if n % n_p or bs % n_p:
        raise ValueError(
            f"global rows {n} and batch {bs} must divide the mesh size {n_p}"
        )
    n_local, bs_local = n // n_p, bs // n_p

    def _log_shard0(sidx, t, loss, log_every):
        if int(sidx) == 0:
            trainer._maybe_log(t, loss, log_every)

    def run_body(params, state, bkey, *data):
        if n_p > 1:
            skey = jax.random.fold_in(bkey, jax.lax.axis_index(DATA_AXIS))
        else:
            skey = bkey  # the exact single-device batch stream

        def body(carry, t):
            params, state = carry
            idx = batch_indices(skey, t, n_local, bs_local)
            batch = tuple(a[idx] for a in data)
            if n_p == 1:
                # trace the existing step verbatim: P=1 stays bit-identical
                params, state, loss = trainer._step(params, state, batch)
            else:
                loss, grads = jax.value_and_grad(trainer._loss_fn)(
                    params, *batch
                )
                if quantized:
                    grads = jax.tree.map(
                        lambda g: gc.quantized_psum(g, DATA_AXIS), grads
                    )
                else:
                    grads = jax.lax.psum(grads, DATA_AXIS)
                grads = jax.tree.map(lambda g: g / n_p, grads)
                loss = jax.lax.pmean(loss, DATA_AXIS)
                params, state, _ = opt.update(
                    trainer._ocfg, grads, state, params
                )
            if log_every:
                if n_p == 1:
                    jax.debug.callback(trainer._maybe_log, t, loss,
                                       np.int64(log_every))
                else:
                    # post-pmean loss is replicated; only shard 0 prints
                    jax.debug.callback(
                        _log_shard0, jax.lax.axis_index(DATA_AXIS),
                        t, loss, np.int64(log_every),
                    )
            return (params, state), loss

        (params, state), losses = jax.lax.scan(
            body, (params, state), jnp.arange(steps)
        )
        return params, state, losses

    return jax.jit(
        jax.shard_map(
            run_body, mesh=mesh,
            in_specs=(P(), P(), P()) + (P(DATA_AXIS),) * n_data,
            out_specs=(P(), P(), P()),
            check_vma=False,
        ),
        donate_argnums=(0, 1),
    )


def dp_wire_report(params, n_devices: int, *, n_bits: int = 8,
                   block: int = 64) -> dict:
    """Per-step gradient-exchange wire bytes, from static leaf shapes.

    The quantized exchange all-gathers each device's full quantized
    gradient (int payload + one fp32 scale per ``block`` values), so a
    device receives ``(P-1) * (q + s)`` bytes per step; the fp32
    baseline is a ring all-reduce at ``2 * (P-1)/P * 4n`` bytes per
    device. Static accounting — the traced program moves exactly these
    payloads, there is nothing dynamic to measure.
    """
    q_bytes = scale_bytes = f32_bytes = 0
    for leaf in jax.tree.leaves(params):
        size = int(np.prod(np.shape(leaf))) if np.shape(leaf) else 1
        blocks = -(-size // block)
        q_bytes += blocks * block * n_bits // 8
        scale_bytes += blocks * 4
        f32_bytes += size * 4
    p = max(int(n_devices), 1)
    quant = (q_bytes + scale_bytes) * (p - 1)
    fp32 = 2 * f32_bytes * (p - 1) // p
    return {
        "n_devices": p,
        "n_bits": n_bits,
        "block": block,
        "grad_fp32_bytes": f32_bytes,
        "quantized_bytes_per_step": quant,
        "fp32_bytes_per_step": fp32,
        "wire_ratio": (fp32 / quant) if quant else float("inf"),
    }


# ---------------------------------------------------------------------------
# (2) species/block-row sharded guarantee engine
# ---------------------------------------------------------------------------
#: per-kernel dispatch plan: for every positional arg and every output,
#: ``(species_axis, row_axis)`` — ``None`` replicates (basis, scalars).
#: ``x64`` mirrors the base engine's ``jax.enable_x64`` scopes: the
#: projection (fp64 under interpret mode) and the fp64 selection math;
#: correction/replay inputs are fp32/int32.
_KERNEL_PLANS = {
    "project": dict(
        args=((0, 1), (0, None)), outs=((0, 1),), x64=True),
    "select": dict(
        args=((0, 1), (0, 1), (0, 1), (0, 1), (0, 1), (0, None), None, None),
        outs=((0, 1), (0, 1), (0, 1)), x64=True),
    "correct": dict(
        args=((0, 1), (0, 1), (0, 1), (0, 1), (0, None)),
        outs=((0, 1),), x64=False),
    "apply": dict(
        args=((0, 1), (0, 1), (0, None)), outs=((0, 1),), x64=False),
}


def _split_points(total: int, parts: int) -> list[int]:
    """Balanced contiguous split boundaries (deterministic)."""
    return [(total * i) // parts for i in range(parts + 1)]


def _chunk_plan(s: int, nb: int, n_shards: int) -> list[tuple]:
    """(s0, s1, r0, r1) extents: species-major, rows split only when
    shards outnumber species. Concatenating per-chunk results restores
    the batched layout exactly (contiguous, in order)."""
    n_s = max(1, min(s, n_shards))
    n_r = max(1, min(n_shards // n_s, nb))
    sb = _split_points(s, n_s)
    rb = _split_points(nb, n_r)
    return [
        (sb[i], sb[i + 1], rb[j], rb[j + 1])
        for i in range(n_s)
        for j in range(n_r)
        if sb[i + 1] > sb[i] and rb[j + 1] > rb[j]
    ]


class ShardedGuaranteeEngine(gae.GuaranteeEngine):
    """GuaranteeEngine whose batched kernel dispatches run one program
    per shard over species (and block rows), placed round-robin on the
    mesh devices.

    The GBATC kernels are per-species and per-block-row pure (projection
    is a per-species GEMM; selection cumsums/argmaxes run within a block
    row; correction is a per-row masked GEMM), so splitting the batch
    into contiguous chunks and concatenating the fetched results is
    bitwise identical to the single batched dispatch — the CSR
    artifacts, and therefore the serialized container, match the
    default engine **byte for byte** (asserted in tier-1).

    Prepared tensors are staged on *host* (the ``_stage`` seam), so no
    device ever holds the full (S, NB, D) problem: each dispatch
    uploads only its shard's chunk to its device. All chunk programs
    are dispatched asynchronously before any result is fetched.

    ``n_shards`` decouples chunk count from device count (defaults to
    the device count) — CI uses it to exercise the chunked path on one
    device, where bitwise identity is just as binding.
    """

    def __init__(self, mesh: Optional[Mesh] = None,
                 devices: Optional[Sequence] = None,
                 n_shards: Optional[int] = None, **kw):
        if devices is None:
            devices = (list(mesh.devices.flat) if mesh is not None
                       else jax.devices())
        self._devices = list(devices)
        self._n_shards = int(n_shards) if n_shards else len(self._devices)
        if self._n_shards < 1:
            raise ValueError(f"n_shards must be >= 1, got {self._n_shards}")
        super().__init__(**kw)

    # prepared tensors stay host-resident; dispatch stages per-shard chunks
    def _stage(self, arr):
        return np.asarray(arr)

    def _dispatch(self, kernel: str, *args):
        plan = _KERNEL_PLANS[kernel]
        fn = getattr(self, f"_{kernel}_jit")
        # problem extents from the first fully-chunked arg (S, NB, ...)
        lead = next(
            a for a, ax in zip(args, plan["args"])
            if ax is not None and ax[1] is not None
        )
        s, nb = int(lead.shape[0]), int(lead.shape[1])
        chunks = _chunk_plan(s, nb, self._n_shards)

        def stage_and_call():
            pending = []
            for i, (s0, s1, r0, r1) in enumerate(chunks):
                dev = self._devices[i % len(self._devices)]
                chunk_args = []
                for arg, ax in zip(args, plan["args"]):
                    if ax is None:
                        chunk_args.append(arg)
                        continue
                    s_ax, r_ax = ax
                    sl = arg[s0:s1]
                    if r_ax is not None:
                        sl = sl[:, r0:r1]
                    chunk_args.append(
                        jax.device_put(np.ascontiguousarray(sl), dev)
                    )
                pending.append(fn(*chunk_args))  # async: one program/shard
            return pending

        if plan["x64"]:
            with jax.enable_x64(True):
                pending = stage_and_call()
                fetched = [jax.tree.map(np.asarray, out) for out in pending]
        else:
            pending = stage_and_call()
            fetched = [jax.tree.map(np.asarray, out) for out in pending]

        return self._concat(fetched, chunks, plan["outs"])

    @staticmethod
    def _concat(fetched, chunks, out_axes):
        """Reassemble per-chunk results: rows within a species group
        first (axis 1), then species groups (axis 0)."""
        single = len(out_axes) == 1
        outs = []
        for k, ax in enumerate(out_axes):
            parts = [f if single else f[k] for f in fetched]
            by_species: dict[int, list] = {}
            for (s0, _s1, _r0, _r1), p in zip(chunks, parts):
                by_species.setdefault(s0, []).append(p)
            groups = [
                rows[0] if len(rows) == 1 else np.concatenate(rows, axis=1)
                for _s0, rows in sorted(by_species.items())
            ]
            outs.append(
                groups[0] if len(groups) == 1
                else np.concatenate(groups, axis=0)
            )
        return outs[0] if single else tuple(outs)


# ---------------------------------------------------------------------------
# (3) streaming sharded ingest buffer (mesh-aware fit_stream)
# ---------------------------------------------------------------------------
class ShardedBlockStore:
    """Row-sharded device landing buffer for two-pass streaming ingest.

    ``append`` writes one chunk's blocks into the next rows of a
    ``("data",)``-sharded (NB, S, bt, ph, pw) device array via a donated
    ``dynamic_update_slice`` program — the host touches one chunk at a
    time and the full normalized field only ever exists sharded across
    the mesh. Row counts must divide the mesh size so every device owns
    an equal contiguous row range (raise early, not mid-ingest).

    The update program is cached per chunk shape (uniform chunking
    traces once; a ragged tail traces one extra program), and the row
    cursor is a traced scalar, so appends never retrace per chunk.
    """

    def __init__(self, nb: int, tail_shape: tuple, mesh: Mesh,
                 dtype=jnp.float32):
        n_p = mesh_size(mesh)
        if nb % n_p:
            raise ValueError(
                f"streamed block count {nb} does not divide the mesh size "
                f"{n_p}; choose a chunking/geometry with NB % P == 0"
            )
        self.nb = int(nb)
        self.mesh = mesh
        self._sharding = data_sharding(mesh)
        self._rows = 0
        self._buf = jax.device_put(
            jnp.zeros((self.nb, *tail_shape), dtype), self._sharding
        )
        self._programs: dict[tuple, object] = {}

    def _update_program(self, part_shape: tuple):
        prog = self._programs.get(part_shape)
        if prog is None:
            @partial(jax.jit, donate_argnums=(0,),
                     out_shardings=self._sharding)
            def prog(buf, part, row):
                start = (row,) + (jnp.int32(0),) * (buf.ndim - 1)
                return jax.lax.dynamic_update_slice(buf, part, start)

            self._programs[part_shape] = prog
        return prog

    def append(self, part: np.ndarray) -> None:
        part = jnp.asarray(np.ascontiguousarray(part), dtype=self._buf.dtype)
        if self._rows + part.shape[0] > self.nb:
            raise ValueError(
                f"append overflows the store: {self._rows} + "
                f"{part.shape[0]} > {self.nb} rows"
            )
        self._buf = self._update_program(part.shape)(
            self._buf, part, jnp.int32(self._rows)
        )
        self._rows += int(part.shape[0])

    def finish(self):
        """The filled sharded array; raises if rows are missing."""
        if self._rows != self.nb:
            raise ValueError(
                f"store holds {self._rows} of {self.nb} block rows"
            )
        return self._buf

    def per_device_bytes(self) -> dict[int, int]:
        """Resident bytes per device id — the ingest memory high-water."""
        out: dict[int, int] = {}
        for shard in self._buf.addressable_shards:
            did = int(shard.device.id)
            out[did] = out.get(did, 0) + int(shard.data.nbytes)
        return out


# ---------------------------------------------------------------------------
# demo: per-device ingest memory vs single device (quickstart step 9)
# ---------------------------------------------------------------------------
def demo() -> None:  # pragma: no cover - exercised via the quickstart
    from repro.core.pipeline import GBATCPipeline, PipelineConfig
    from repro.data import s3d

    mesh = host_mesh()
    n_p = mesh_size(mesh)
    cfg = PipelineConfig(conv_channels=(8, 16), ae_steps=40, corr_steps=20,
                         batch_size=32)
    scfg = s3d.S3DConfig(n_species=2, n_time=16, height=40, width=40, seed=0)
    loader = s3d.S3DChunkLoader(scfg, chunk_frames=4)
    pipe = GBATCPipeline(cfg, n_species=2, mesh=mesh)
    pipe.fit_stream(loader)
    store_bytes = {
        int(sh.device.id): int(sh.data.nbytes)
        for sh in pipe._blocks.addressable_shards
    }
    total = int(pipe._blocks.nbytes)
    peak = max(store_bytes.values())
    print(f"mesh fit on {n_p} {jax.default_backend()} device(s): "
          f"normalized field "
          f"{total} bytes total, per-device ingest high-water "
          f"{peak} bytes ({peak / total:.0%} of single-device)")
    rep = pipe.compress(target_nrmse=1e-3)
    print(f"sharded compress: mean NRMSE {rep.mean_nrmse:.2e} "
          f"(target 1e-3), CR {rep.compression_ratio:.1f}x")


if __name__ == "__main__":  # pragma: no cover
    demo()
