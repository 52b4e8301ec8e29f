"""meshsolve — pod-scale sharded inference on the live solve path.

`parallel/` holds the mesh/sharding substrate and the model pipelines
each know how to run over a mesh, but until this layer nothing connected
`MiningConfig` to them: every solve executed on one device. meshsolve is
that connection — the boot-time half (config → validated device mesh →
obs surface) and the dispatch-time half (batch placement, canonical
gather, collective-traffic accounting) that `node/factory.py` and the
pipelines share. The execution pattern follows multi-host GSPMD serving
(SNIPPETS [1]/[3]): annotate `NamedSharding`s on params (rule tables)
and the batch (`batch_sharding`), jit with in/out specs, and let XLA
insert the collectives; the video family additionally runs its denoise
scan under `shard_map` with ring/ulysses sequence parallelism (ops/).

Determinism contract (docs/multichip.md has the full argument):

  dp  shards SAMPLES. Each task's compute stays local to one chip and
      the output gather is a pure layout op, so dp-only layouts are
      bit-identical to mesh-off — proven by tests, not assumed.
  tp/sp  change reduction order (psum / ring accumulation), so each such
      layout is its OWN determinism class — exactly like canonical_batch
      — pinned per (family, layout) by graphlint goldens. A fleet mines
      one layout per model; mesh=None is byte-for-byte the pre-mesh
      program (the goldens pin that too).

The sharded probe runners at the bottom are this module's executable
spec: tiny real XLA programs (GSPMD image-shaped, shard_map video-shaped)
whose math is layout-invariant BY CONSTRUCTION (per-sample PRNG keyed on
global indices, concatenation-only collectives, integer cross-shard
reductions — exact in any order). The byte-equality suite and simnet's
mesh scenarios drive the node path through them, so the machinery
(bucketing, chunking, placement, gather order) is tested separately
from any one model's float behavior.
"""
# detlint: enforce[DET101,DET102,DET103,DET104,DET105]
from __future__ import annotations

import logging
from dataclasses import dataclass

import numpy as np

from arbius_tpu.parallel.mesh import (
    AXIS_ORDER,
    MeshSpec,
    abstract_mesh,
    build_mesh,
    mesh_tag,
    validate_axes,
)

log = logging.getLogger("arbius.meshsolve")

_OBS_HELP_DEVICES = ("Devices in the solve mesh (product of the "
                     "configured axis sizes); 0 or absent = single-device")
_OBS_HELP_BYTES = ("Estimated cross-chip collective traffic on the solve "
                   "path, by mesh axis — compile-time byte-count model "
                   "(docs/multichip.md), not a profiler")


def boot_mesh(mesh_cfg: dict | None, *, registry=None):
    """Config → live device mesh, or None for the single-device path.

    Validates the requested shape against `jax.device_count()` with a
    boot-quality error (parallel/mesh.validate_axes) instead of letting
    a bad shape die as a deep XLA reshape failure, builds the mesh over
    the first ``prod(axes)`` local devices, and (when an obs registry is
    given) publishes `arbius_mesh_devices`."""
    if registry is not None:
        n = 1
        if mesh_cfg:
            for v in mesh_cfg.values():
                n *= int(v)
        registry.gauge("arbius_mesh_devices", _OBS_HELP_DEVICES).set(
            float(n if mesh_cfg else 0))
    if not mesh_cfg:
        return None
    import jax

    sizes = validate_axes(dict(mesh_cfg), jax.device_count(),
                          where="mesh config")
    spec = MeshSpec(dp=sizes["dp"], sp=sizes["sp"], tp=sizes["tp"],
                    pp=sizes["pp"])
    want = sizes["pp"] * sizes["dp"] * sizes["sp"] * sizes["tp"]
    devices = jax.devices()[:want] if want < jax.device_count() else None
    mesh = build_mesh(spec, devices=devices)
    log.info("solve mesh up: %s over %d devices", mesh_tag(mesh), want)
    return mesh


# non-dp axes are goldened at this size: every per-layout golden is
# traced over abstract_mesh(MeshSpec(axis=2, ...)) — see each family's
# trace_specs(). dp is the one size-free axis (sample-local compute,
# layout-only gather: bytes are dp-size-invariant — on CPU, test-pinned;
# NOT on the chip: on four v5e chips dp=4 at canonical_batch 4 solved and
# claimed but its CIDs matched neither single-chip canonical_batch 4 nor
# 1, docs/multichip.md); a tp/sp size changes the reduction order, i.e.
# the program, so an unshipped SIZE is an unshipped determinism class
# exactly like an unshipped layout.
GOLDEN_AXIS_SIZE = 2


def golden_mesh(axes):
    """Abstract mesh at the goldened size for a MESH_LAYOUTS entry
    (None for the empty layout). THE constructor every `trace_specs()`
    uses, so the meshes the goldens are traced over and the sizes
    `check_mesh_contract` admits can never drift apart."""
    if not axes:
        return None
    return abstract_mesh(MeshSpec(**{a: GOLDEN_AXIS_SIZE for a in axes}))


def golden_layout_tag(axes) -> str:
    """Golden-key mesh tag for a MESH_LAYOUTS entry ("single" for ())."""
    return mesh_tag(golden_mesh(axes)) if axes else "single"


def check_mesh_contract(mesh, contracts: dict, canonical_batch: int) -> None:
    """Boot-time audit of the configured mesh against each enabled
    family's shipped mesh contract. `contracts` maps template name →
    the family's pipeline module, which publishes that contract as data
    (`MESH_LAYOUTS`, `MESH_BATCH_HARD`) next to its `trace_specs()` —
    node/factory.mesh_contracts builds the dict from its builder table,
    so there is exactly one list of families.

    Two gates, both at boot rather than at first task:

      * the active layout (axes of size > 1) must be one of the family's
        `MESH_LAYOUTS`, and every non-dp axis must run at the goldened
        size (`GOLDEN_AXIS_SIZE`): every shipped (family, layout) pair
        has a graphlint golden pinning its determinism class, and a
        layout OR size with no golden could emit CIDs no other honest
        miner reproduces — the contest scenario the whole gate exists
        to prevent.
      * dp must divide the canonical batch. A family whose batch axis is
        hard-partitioned (`MESH_BATCH_HARD`, the video shard_map) fails
        loudly; everyone else degrades to a replicated batch (dp lanes
        idle) with a warning."""
    if mesh is None:
        return
    active = tuple(a for a in AXIS_ORDER if mesh.shape.get(a, 1) > 1)
    dp = mesh.shape.get("dp", 1)
    if contracts:
        for a in active:
            if a != "dp" and mesh.shape[a] != GOLDEN_AXIS_SIZE:
                raise ValueError(
                    f"mesh {a}={mesh.shape[a]} is not a goldened size: "
                    f"the per-layout graphlint goldens pin {a}="
                    f"{GOLDEN_AXIS_SIZE}, and a different {a} size is a "
                    "different reduction order — a determinism class no "
                    "golden pins (docs/multichip.md; dp is the only "
                    "size-free axis)")
    batch_hard = []
    for family in sorted(contracts):
        mod = contracts[family]
        layouts = getattr(mod, "MESH_LAYOUTS", ())
        if active not in layouts:
            shipped = ", ".join("·".join(l) for l in layouts) or "(none)"
            raise ValueError(
                f"mesh layout {'·'.join(active) or '(all axes 1)'} is "
                f"not a shipped determinism class for template {family} "
                f"(shipped: {shipped}): no graphlint golden pins its "
                "program, so its CIDs are outside the cross-miner "
                f"contract — disable {family}, change the mesh, or ship "
                "the layout (MESH_LAYOUTS + regenerated goldens, "
                "docs/multichip.md)")
        if dp > 1 and canonical_batch % dp and \
                getattr(mod, "MESH_BATCH_HARD", False):
            batch_hard.append(family)
    if batch_hard:
        raise ValueError(
            f"mesh dp={dp} cannot shard canonical_batch="
            f"{canonical_batch} for template(s) {batch_hard}: the "
            "shard_map batch axis hard-partitions over dp — set "
            f"canonical_batch to a multiple of {dp}")
    if dp > 1 and canonical_batch % dp and contracts:
        log.warning(
            "canonical_batch=%d is not divisible by mesh dp=%d — solve "
            "batches fall back to a replicated batch axis (dp lanes "
            "idle); set canonical_batch to a multiple of dp to actually "
            "scale", canonical_batch, dp)


# -- dispatch-time placement ------------------------------------------------

def batch_specs(mesh, batch: int):
    """(in_sharding, out_sharding) factory pair for a bucket of size
    `batch`: shard the leading axis over dp when it divides, else
    replicate (the degrade keeps under-filled buckets runnable — dp
    lanes idle rather than erroring). Returns callables taking ndim so
    arguments of different rank share one decision."""
    from arbius_tpu.parallel.sharding import batch_sharding, replicated

    dp = mesh.shape.get("dp", 1)
    sharded = dp > 1 and batch % dp == 0

    def spec(ndim: int):
        return batch_sharding(mesh, ndim) if sharded else replicated(mesh)

    return spec, sharded


def shard_batch(mesh, *arrays):
    """Place batch-leading arrays for one solve dispatch: dp-sharded
    when the batch divides, replicated otherwise (one decision for the
    whole argument list — mixed placement would deadlock the program).
    The single-device path (`mesh=None`) returns the arrays untouched."""
    if mesh is None:
        return arrays
    import jax

    spec, _ = batch_specs(mesh, int(np.shape(arrays[0])[0]))
    return tuple(jax.device_put(a, spec(np.ndim(a))) for a in arrays)


def gather_canonical(out) -> np.ndarray:
    """Fully-replicated gather of a (possibly dp-sharded) device result
    in canonical order: jax arrays are logically ordered regardless of
    layout, so `np.asarray` IS the order-preserving gather — sample i of
    the output is sample i of the input bucket on every mesh shape.
    Named so call sites say what they mean."""
    return np.asarray(out)


# -- collective-traffic accounting ------------------------------------------

def estimate_collective_bytes(mesh, out_shape, out_dtype, params=None,
                              *, batch_sharded: bool = True,
                              wire_dtype=None) -> dict[str, int]:
    """Per-dispatch cross-chip traffic estimate, by mesh axis.

    A compile-time byte-count model (the obs satellite's contract —
    docs/observability.md): order-of-magnitude planning signal for
    dashboards, not a profiler. Pure in (mesh, shapes, param placement),
    all fixed after boot — so call sites compute it once per bucket
    (`record_bucket_estimate`), not per dispatch.

      dp  the replicated gather of the output bucket: each chip holds
          1/dp of the result and receives the rest. Zero when the bucket
          degraded to a replicated batch (`batch_sharded=False`) — the
          gather is then chip-local.
      sp  ring/halo traffic of the frame-sharded activations, proxied
          by the same gather model on the output.
      tp  one collective per rule-sharded kernel pair; the moved
          activation slab is proxied by the kernel's own byte count
          (exactly computable from the param tree at placement time,
          and of the same order as the activation at canonical batch).

    `wire_dtype` overrides the per-ELEMENT width of the tp allreduce
    term: when the tp path runs an EQuARX-style quantized collective
    (docs/quantization.md) the slab moves as 1-byte elements regardless
    of the leaf dtype, and `arbius_collective_bytes_total{axis="tp"}`
    must report the actual wire bytes, not the full-width assumption.
    None (the default) keeps the historic leaf-dtype-width model.

    Axes of size 1 contribute nothing. Returns {axis: bytes}."""
    est: dict[str, int] = {}
    if mesh is None:
        return est
    out_bytes = int(np.prod(out_shape)) * np.dtype(out_dtype).itemsize
    if batch_sharded:
        for axis in ("dp", "sp"):
            n = mesh.shape.get(axis, 1)
            if n > 1:
                est[axis] = out_bytes * (n - 1) // n
    tp = mesh.shape.get("tp", 1)
    if tp > 1 and params is not None:
        import jax

        wire_width = np.dtype(wire_dtype).itemsize \
            if wire_dtype is not None else None
        sharded = 0
        for leaf in jax.tree_util.tree_leaves(params):
            sh = getattr(leaf, "sharding", None)
            spec = getattr(sh, "spec", None)
            if spec is not None and any(
                    s == "tp" or (isinstance(s, tuple) and "tp" in s)
                    for s in spec):
                width = wire_width if wire_width is not None \
                    else leaf.dtype.itemsize
                sharded += int(np.prod(leaf.shape)) * width
        if sharded:
            # ring allreduce moves 2·(tp-1)/tp of the slab per collective
            est["tp"] = 2 * sharded * (tp - 1) // tp
    return est


def record_bucket_estimate(cache: dict, bucket_key, mesh, out, batch: int,
                           *, params=None, wire_dtype=None,
                           tag: str | None = None) -> None:
    """Record one dispatch's traffic, estimating at most once per bucket:
    the estimate is pure in (mesh, bucket shape, param placement), so the
    first dispatch of a bucket walks the param tree and later dispatches
    reuse the cached {axis: bytes} — the hot solve loop never re-walks
    hundreds of leaves to recompute a constant. `batch_sharded` comes
    from the same `batch_specs` decision the bucket compiled with, so a
    replicated-degrade bucket is not charged dp/sp gathers that never
    cross chips. `wire_dtype` rides through to the tp term for
    quantized-collective buckets (see estimate_collective_bytes).
    `tag` is the bucket's executable-cache tag: when a `PerfScope` is
    installed (docs/perfscope.md), the per-dispatch wire bytes join the
    bucket's PerfCard through it — the same per-bucket cache, no second
    walk."""
    if mesh is None:
        return
    est = cache.get(bucket_key)
    if est is None:
        _, sharded = batch_specs(mesh, batch)
        est = estimate_collective_bytes(mesh, out.shape, out.dtype,
                                        params=params, batch_sharded=sharded,
                                        wire_dtype=wire_dtype)
        cache[bucket_key] = est
    record_collective_bytes(est, tag=tag)


def record_collective_bytes(est: dict[str, int],
                            tag: str | None = None) -> None:
    """Add one dispatch's estimated traffic to
    `arbius_collective_bytes_total{axis}` in the ambient obs registry
    (no-op outside a node context — library code stays node-free, the
    same pattern as `obs.span`). `tag` additionally lands the estimate
    on the bucket's PerfCard when a perfscope is installed."""
    if not est:
        return
    from arbius_tpu.obs import current_obs

    obs = current_obs()
    if obs is None:
        return
    c = obs.registry.counter("arbius_collective_bytes_total",
                             _OBS_HELP_BYTES, labelnames=("axis",))
    for axis, n in est.items():
        c.inc(float(n), axis=axis)
    if obs.perfscope is not None:
        obs.perfscope.record_collectives(tag, est)


# -- sharded probe runners --------------------------------------------------
#
# Tiny REAL sharded solve programs with the Runner dispatch/finalize
# surface (node/solver.py), used as layout-invariance oracles: the node
# path must produce byte-identical CIDs at mesh-off / dp-only / dp·tp
# for these by construction, so any drift is a machinery bug (ordering,
# padding, gather), never float luck. Simnet's mesh scenarios reuse
# them so their runs solve the same programs the equality tests pin.

_PROBE_DIM = 8


def _probe_params(dim: int = _PROBE_DIM) -> np.ndarray:
    # fixed, seed-free weights: the probe's identity is its program
    return (np.arange(dim * dim, dtype=np.float32).reshape(dim, dim)
            % 7.0) / 7.0 - 0.5


@dataclass
class _ProbeBase:
    """Shared probe surface: canonical-batch Runner protocol over a
    jitted sharded program. `gate` (e.g. simnet's plane.runner_gate) is
    called once per dispatch so fault injection composes. `mode` is the
    precision mode (docs/quantization.md): "bf16" is the exact historic
    probe program (goldens unchanged); int8/fp8 quantize the probe
    weights and dequantize inside the jit — a different program, its
    own golden, deterministic in (input, seed, layout, mode)."""

    mesh: object = None
    out_name: str = "out-1.png"
    gate: object = None
    mode: str = "bf16"

    def __call__(self, hydrated: dict, seed: int) -> dict:
        return self.finalize(self.dispatch([(hydrated, seed)]), 1)[0]

    def run_batch(self, items: list) -> list[dict]:
        return self.finalize(self.dispatch(items), len(items))

    def finalize(self, dev, n_real: int) -> list[dict]:
        arr = gather_canonical(dev)
        return [{self.out_name: b"\x89PNG" + arr[i].tobytes()}
                for i in range(n_real)]

    def _seeds(self, items) -> np.ndarray:
        # fold the prompt into the per-sample stream like taskid2seed
        # feeds real runners: bytes must depend on (input, seed)
        import zlib

        return np.asarray(
            [(s ^ zlib.crc32(str(h.get("prompt", "")).encode())) & 0xFFFFFFFF
             for h, s in items], dtype=np.uint32)


class ShardedImageProbe(_ProbeBase):
    """GSPMD image-shaped probe: per-sample PRNG draw + column-parallel
    matmul + tanh, jitted with NamedSharding in/out specs — the SD-1.5
    execution pattern in miniature. Column-parallel tp keeps every
    reduction chip-local (the tp collective is concatenation-only), so
    the bytes are exactly layout-invariant."""

    def __init__(self, mesh=None, out_name: str = "out-1.png", gate=None,
                 mode: str = "bf16"):
        from arbius_tpu.quant import validate_mode

        super().__init__(mesh=mesh, out_name=out_name, gate=gate,
                         mode=validate_mode(mode))
        self._fns: dict[int, object] = {}
        self._est: dict[int, dict] = {}
        self._params = None

    def _param_sharding(self):
        from jax.sharding import NamedSharding, PartitionSpec as P

        tp = self.mesh.shape.get("tp", 1)
        col = tp > 1 and _PROBE_DIM % tp == 0
        # column-parallel over tp when it divides: concat-gather, no psum
        kernel = NamedSharding(self.mesh, P(None, "tp") if col else P())
        if self.mode == "bf16":
            return kernel
        # quantized tree: int8/fp8 kernel keeps the column split, the
        # per-output-channel f32 scale shards over the same tp axis
        scale = NamedSharding(self.mesh, P("tp") if col else P())
        return {"qs": scale, "qv": kernel}

    def _fn(self, batch: int):
        return self._get_fn(batch)[0]

    def bucket_tag(self, batch: int) -> str:
        from arbius_tpu.quant import mode_tag

        return f"meshprobe.img.b{batch}" + mode_tag(self.mode)

    def cache_tag(self, hydrated: dict, batch: int) -> str:
        """The tag a dispatch of this bucket would cache under — the
        scheduler's cross-life disk-warm join key
        (docs/compile-cache.md)."""
        del hydrated  # probe buckets key on batch alone
        return self.bucket_tag(batch)

    def _get_fn(self, batch: int, aot_args=None):
        """(fn, warm, tag) via the shared jit-cache obs helper
        (docs/observability.md) — the probes report warm-executable
        reuse exactly like the model pipelines, so the simnet flood
        sees real jit-cache counters (and, with an AOT cache installed,
        real disk-tier traffic)."""
        from arbius_tpu.obs import jit_cache_get

        return jit_cache_get(self._fns, batch,
                             lambda: self._build_fn(batch),
                             tag=self.bucket_tag(batch),
                             aot_args=aot_args)

    def _build_fn(self, batch: int):
        import jax
        import jax.numpy as jnp

        mode = self.mode

        def run(params, seeds):
            if mode != "bf16":
                from arbius_tpu.quant import dequantize_leaf

                # int8/fp8 kernel → f32 via the f32-scale dequant
                # (GRAPH407 contract); the bf16 program below stays
                # byte-identical to the pre-quant probe
                params = dequantize_leaf(params)

            def per(k):
                key = jax.random.PRNGKey(k)
                noise = jax.random.normal(key, (_PROBE_DIM, _PROBE_DIM),
                                          jnp.float32)
                return jnp.tanh(noise @ params)

            return jax.vmap(per)(seeds)

        if self.mesh is None:
            return jax.jit(run)
        spec, _ = batch_specs(self.mesh, batch)
        return jax.jit(run,
                       in_shardings=(self._param_sharding(), spec(1)),
                       out_shardings=spec(3))

    def _wire_dtype(self):
        """Quantized modes move 1-byte elements on the tp wire — the
        collective-byte model reports actual wire width
        (docs/quantization.md wire-byte accounting)."""
        from arbius_tpu.quant import storage_dtype

        return storage_dtype(self.mode) if self.mode != "bf16" else None

    def dispatch(self, items: list):
        if self.gate is not None:
            self.gate()
        import jax

        from arbius_tpu.obs import timed_dispatch

        if self._params is None:
            raw = _probe_params()
            if self.mode != "bf16":
                from arbius_tpu.quant import quantize_leaf

                raw = quantize_leaf(raw, self.mode)
            self._params = jax.device_put(
                raw, self._param_sharding()) if self.mesh is not None \
                else jax.device_put(raw)
        seeds = self._seeds(items)
        (seeds_dev,) = shard_batch(self.mesh, seeds)
        fn, warm, tag = self._get_fn(
            len(items), aot_args=lambda: (self._params, seeds_dev))
        with timed_dispatch(warm, tag):
            out = fn(self._params, seeds_dev)
        record_bucket_estimate(self._est, len(items), self.mesh, out,
                               len(items), params=self._params,
                               wire_dtype=self._wire_dtype(), tag=tag)
        return out


class ShardedSeqProbe(_ProbeBase):
    """shard_map video-shaped probe: frames shard over sp, samples over
    dp, noise keyed by (sample, GLOBAL frame) exactly like the UNet3D
    pipeline's sp-invariant stream, plus an INTEGER psum over sp (exact
    in any reduction order) so a real named-axis collective lives in the
    shipped program graphlint fingerprints."""

    frames: int = 4

    def __init__(self, mesh=None, out_name: str = "out-1.png", gate=None,
                 frames: int = 4, mode: str = "bf16"):
        from arbius_tpu.quant import validate_mode

        super().__init__(mesh=mesh, out_name=out_name, gate=gate,
                         mode=validate_mode(mode))
        self.frames = frames
        self._fns: dict[int, object] = {}
        self._est: dict[int, dict] = {}
        self._params = None

    def _fn(self, batch: int):
        return self._get_fn(batch)[0]

    def bucket_tag(self, batch: int) -> str:
        from arbius_tpu.quant import mode_tag

        return f"meshprobe.seq.b{batch}.f{self.frames}" \
            + mode_tag(self.mode)

    def cache_tag(self, hydrated: dict, batch: int) -> str:
        """Scheduler's cross-life disk-warm join key
        (docs/compile-cache.md) — see ShardedImageProbe.cache_tag."""
        del hydrated
        return self.bucket_tag(batch)

    def _get_fn(self, batch: int, aot_args=None):
        from arbius_tpu.obs import jit_cache_get

        def build():
            # shard_map hard-partitions the batch axis — an under-filled
            # bucket (batch % dp != 0) degrades to the single-device
            # program, whose bytes the shard_map build matches by
            # construction
            mesh = self.mesh
            if mesh is not None and batch % mesh.shape.get("dp", 1):
                mesh = None
            return build_seq_probe_fn(mesh, self.frames, mode=self.mode)

        return jit_cache_get(self._fns, batch, build,
                             tag=self.bucket_tag(batch),
                             aot_args=aot_args)

    def dispatch(self, items: list):
        if self.gate is not None:
            self.gate()
        import jax

        from arbius_tpu.obs import timed_dispatch

        if self._params is None:
            raw = _probe_params()
            if self.mode != "bf16":
                from arbius_tpu.quant import quantize_leaf

                raw = quantize_leaf(raw, self.mode)
            self._params = jax.device_put(raw)
        seeds = self._seeds(items)
        (seeds_dev,) = shard_batch(self.mesh, seeds)
        fn, warm, tag = self._get_fn(
            len(items), aot_args=lambda: (self._params, seeds_dev))
        with timed_dispatch(warm, tag):
            out = fn(self._params, seeds_dev)
        record_bucket_estimate(self._est, len(items), self.mesh, out,
                               len(items), tag=tag)
        return out


def build_seq_probe_fn(mesh, frames: int, *, psum_axes=("sp",),
                       mode: str = "bf16"):
    """The seq probe's jitted program, exposed for graphlint: a
    shard_map over (dp, sp) whose temporal stream is keyed by global
    frame index and whose one cross-shard reduction is an int32 psum
    over `psum_axes` (canonical single-axis order — GRAPH403's beat).
    `psum_axes` is parameterizable so the rule test can trace the same
    program with a deliberately non-canonical multi-axis reduction.

    `mode` != "bf16" is the quantized determinism class
    (docs/quantization.md): params arrive as the quantized {"qs","qv"}
    tree and dequantize in-program, and — when frames shard over sp —
    a cross-shard temporal summary travels through the EQuARX-style
    `quantized_ring_allreduce`, putting a real quantized collective in
    the shipped program the per-mode golden pins. The default is the
    byte-identical pre-quant program."""
    import jax
    import jax.numpy as jnp

    sp = mesh.shape.get("sp", 1) if mesh is not None else 1
    if frames % sp:
        raise ValueError(f"frames {frames} not divisible by sp={sp}")
    t_local = frames // sp

    def run(params, seeds):
        if mode != "bf16":
            from arbius_tpu.quant import dequantize_leaf

            params = dequantize_leaf(params)
        if sp > 1:
            frame0 = jax.lax.axis_index("sp") * t_local
        else:
            frame0 = 0

        def per(k):
            key = jax.random.PRNGKey(k)
            return jax.vmap(lambda f: jnp.tanh(jax.random.normal(
                jax.random.fold_in(key, f), (_PROBE_DIM, _PROBE_DIM),
                jnp.float32) @ params))(frame0 + jnp.arange(t_local))

        x = jax.vmap(per)(seeds)
        if mode != "bf16" and sp > 1:
            from arbius_tpu.parallel.collectives import \
                quantized_ring_allreduce

            # fold a cross-shard temporal mean through the quantized
            # collective: the 1-byte wire is where the tp/sp byte
            # savings come from, and the ring schedule is fixed per
            # layout, so the fold is deterministic — this (layout,
            # mode) program is its own golden-pinned class
            m = quantized_ring_allreduce(jnp.mean(x, axis=1), "sp",
                                         mode=mode)
            x = x + m[:, None] * (1.0 / 16.0)
        # integer frame checksum summed across every shard: exact in any
        # reduction order, so the psum cannot move bytes across layouts
        check = jnp.sum((x * 255.0).astype(jnp.int32) & 0xFF,
                        axis=(1, 2, 3), dtype=jnp.int32)
        if mesh is not None:
            check = jax.lax.psum(check, psum_axes)
        return x + (check % 3).astype(jnp.float32)[:, None, None, None]

    if mesh is None:
        return jax.jit(run)
    from jax.experimental.shard_map import shard_map
    from jax.sharding import PartitionSpec as P

    return jax.jit(shard_map(
        run, mesh=mesh,
        in_specs=(P(), P("dp")),
        out_specs=P("dp", "sp"),
        check_rep=False))


# probe mesh layouts shipped with goldens (docs/multichip.md): the img
# probe is the GSPMD image-family shape, the seq probe the shard_map
# video-family shape — its dp2.sp2 layout carries the one REAL int32
# psum in the golden set, pinning GRAPH403's canonical-axis-order beat.
IMG_LAYOUTS: tuple[tuple[str, ...], ...] = ((), ("dp", "tp"))
SEQ_LAYOUTS: tuple[tuple[str, ...], ...] = ((), ("dp", "sp"))


def trace_specs():
    """graphlint trace specs for the probe programs. The probes are
    SHIPPED solve programs — simnet's mesh scenarios drive the real
    node path through them — so each (probe,
    layout) pair gets a golden fingerprint exactly like a model family:
    a schedule or collective change in the machinery shows up as golden
    drift here even before any model's bytes move."""
    import jax
    import jax.numpy as jnp

    from arbius_tpu.models.trace_specs import TraceSpec
    from arbius_tpu.quant import abstract_quantized

    sds = jax.ShapeDtypeStruct

    def param_args(batch: int, mode: str):
        p = sds((_PROBE_DIM, _PROBE_DIM), jnp.float32)
        if mode != "bf16":
            p = abstract_quantized(p, mode)
        return (p, sds((batch,), jnp.uint32))

    def build_img(axes, mode="bf16"):
        def build():
            probe = ShardedImageProbe(mesh=golden_mesh(axes), mode=mode)
            batch = 2 if axes else 1
            return probe._fn(batch), param_args(batch, mode)

        return build

    def build_seq(axes, mode="bf16"):
        def build():
            fn = build_seq_probe_fn(golden_mesh(axes), frames=4,
                                    mode=mode)
            batch = 2 if axes else 1
            return fn, param_args(batch, mode)

        return build

    # bf16 keys carry dtype="float32" (the probes' historic compute
    # dtype tag — goldens unchanged); quantized modes key on the mode,
    # exactly like the model families (docs/quantization.md)
    return [
        TraceSpec(model="meshprobe", entry="img",
                  bucket="b2" if axes else "b1", mesh=golden_layout_tag(axes),
                  dtype="float32", build=build_img(axes))
        for axes in IMG_LAYOUTS
    ] + [
        TraceSpec(model="meshprobe", entry="seq",
                  bucket="b2.f4" if axes else "b1.f4",
                  mesh=golden_layout_tag(axes), dtype="float32",
                  build=build_seq(axes))
        for axes in SEQ_LAYOUTS
    ] + [
        TraceSpec(model="meshprobe", entry="img",
                  bucket="b2" if axes else "b1", mesh=golden_layout_tag(axes),
                  dtype="int8", build=build_img(axes, "int8"))
        for axes in IMG_LAYOUTS
    ] + [
        TraceSpec(model="meshprobe", entry="seq",
                  bucket="b2.f4" if axes else "b1.f4",
                  mesh=golden_layout_tag(axes), dtype="int8",
                  build=build_seq(axes, "int8"))
        for axes in SEQ_LAYOUTS
    ]
