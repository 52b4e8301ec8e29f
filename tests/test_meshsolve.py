"""meshsolve — pod-scale sharded inference on the live solve path.

The determinism contract under test (docs/multichip.md): dp shards
SAMPLES, so a dp-only layout must be BIT-identical to mesh-off; tp/sp
layouts are their own determinism classes, pinned by per-layout
graphlint goldens rather than byte equality — except for the probe
programs, whose math is layout-invariant BY CONSTRUCTION and therefore
pins the machinery (bucketing, chunking, placement, canonical gather)
at every layout. All of this runs on the forced 8-way CPU device
harness (tests/conftest.py), no accelerator involved.
"""
import logging
import pathlib

import numpy as np
import pytest

from arbius_tpu.node.config import ConfigError, MiningConfig, ModelConfig
from arbius_tpu.node.solver import RegisteredModel, solve_cid_batch
from arbius_tpu.obs import Obs, use_obs
from arbius_tpu.parallel import MeshSpec, abstract_mesh, meshsolve, validate_axes
from arbius_tpu.templates.engine import hydrate_input, load_template

GOLDENS_DIR = pathlib.Path(__file__).resolve().parent.parent / "goldens" / "graph"


# -- boot-time validation ---------------------------------------------------

def test_validate_axes_unknown_axis_names_the_registry():
    with pytest.raises(ValueError) as e:
        validate_axes({"dp": 2, "zz": 2})
    msg = str(e.value)
    assert "zz" in msg and "dp" in msg and "tp" in msg


@pytest.mark.parametrize("bad", [0, -1, "2", 2.0, True])
def test_validate_axes_rejects_non_positive_int(bad):
    with pytest.raises(ValueError) as e:
        validate_axes({"dp": bad})
    assert "positive integer" in str(e.value)


def test_validate_axes_device_count_is_one_clear_sentence():
    """The whole point of the satellite: a shape that does not fit the
    visible devices must die with a sentence naming the shape, the
    counts, and the CPU-testing escape hatch — not a deep XLA reshape
    failure."""
    with pytest.raises(ValueError) as e:
        validate_axes({"dp": 4, "tp": 4}, 8)
    msg = str(e.value)
    assert "needs 16 devices" in msg and "jax sees 8" in msg
    assert "--xla_force_host_platform_device_count=16" in msg


def test_boot_mesh_rejects_oversized_shape():
    with pytest.raises(ValueError, match="needs 16 devices"):
        meshsolve.boot_mesh({"dp": 16})


@pytest.mark.parametrize("bad", [{}, {"dp": 0}, {"xx": 2}, "dp2", 2])
def test_mining_config_validates_mesh_at_load(bad):
    with pytest.raises(ConfigError):
        MiningConfig(mesh=bad)


def test_mining_config_accepts_mesh_layouts():
    for mesh in (None, {"dp": 4, "tp": 2}, {"dp": 2, "sp": 2, "tp": 2}):
        assert MiningConfig(mesh=mesh).mesh == mesh


def test_boot_mesh_publishes_device_gauge():
    obs = Obs()
    assert meshsolve.boot_mesh(None, registry=obs.registry) is None
    assert obs.registry.gauge("arbius_mesh_devices").value() == 0.0
    mesh = meshsolve.boot_mesh({"dp": 2, "tp": 2}, registry=obs.registry)
    assert mesh is not None and mesh.shape["dp"] == 2
    assert obs.registry.gauge("arbius_mesh_devices").value() == 4.0


def test_check_mesh_contract_batch_video_fails_image_warns(caplog):
    from arbius_tpu.models.sd15 import pipeline as sd15
    from arbius_tpu.models.video import pipeline as video

    mesh = meshsolve.boot_mesh({"dp": 2})
    # image-only fleet: degrade path, warn but run
    with caplog.at_level(logging.WARNING, logger="arbius.meshsolve"):
        meshsolve.check_mesh_contract(mesh, {"anythingv3": sd15}, 3)
    assert any("not divisible" in r.message for r in caplog.records)
    # video hard-partitions the batch axis (MESH_BATCH_HARD): boot
    # failure, not first-task — at its one shipped dp·sp·tp layout
    mesh3 = meshsolve.boot_mesh({"dp": 2, "sp": 2, "tp": 2})
    with pytest.raises(ValueError, match="zeroscopev2xl"):
        meshsolve.check_mesh_contract(mesh3, {"zeroscopev2xl": video}, 3)
    meshsolve.check_mesh_contract(mesh3, {"zeroscopev2xl": video}, 4)
    meshsolve.check_mesh_contract(None, {"zeroscopev2xl": video}, 3)


def test_check_mesh_contract_rejects_unshipped_layout():
    """An enabled family must not boot in a determinism class that no
    graphlint golden pins: sd15 ships dp and dp·tp, so a dp·sp mesh —
    valid axes, fits the devices — is a boot error naming the family,
    its shipped layouts, and the missing golden."""
    from arbius_tpu.models.sd15 import pipeline as sd15

    mesh = meshsolve.boot_mesh({"dp": 2, "sp": 2})
    with pytest.raises(ValueError) as e:
        meshsolve.check_mesh_contract(mesh, {"anythingv3": sd15}, 2)
    msg = str(e.value)
    assert "anythingv3" in msg and "dp·tp" in msg and "golden" in msg


def test_check_mesh_contract_rejects_ungoldened_axis_size():
    """tp=4 at a shipped LAYOUT is still an unshipped determinism
    class: the goldens pin tp=2, and a 4-way kernel partition is a
    different psum order. dp stays size-free (bytes are dp-invariant
    by the layout argument, so dp4 needs no golden of its own)."""
    from arbius_tpu.models.sd15 import pipeline as sd15

    mesh = meshsolve.boot_mesh({"dp": 2, "tp": 4})
    with pytest.raises(ValueError, match="tp=4"):
        meshsolve.check_mesh_contract(mesh, {"anythingv3": sd15}, 2)
    mesh = meshsolve.boot_mesh({"dp": 4, "tp": 2})
    meshsolve.check_mesh_contract(mesh, {"anythingv3": sd15}, 4)


def test_build_registry_rejects_unshipped_layout():
    """The gate wired end-to-end: config → build_registry dies at boot
    for a (family, layout) pair with no golden, before any runner or
    params exist."""
    from arbius_tpu.node.factory import build_registry

    cfg = MiningConfig(
        models=(ModelConfig(id="0x" + "11" * 32, template="anythingv3",
                            tiny=True),),
        mesh={"dp": 2, "sp": 2})
    with pytest.raises(ValueError, match="anythingv3"):
        build_registry(cfg)


def test_factory_mesh_contracts_cover_every_mesh_family():
    """The contract table rides the builder table: every mesh-capable
    template resolves to a pipeline module that publishes MESH_LAYOUTS
    (robust_video_matting stays single-device on purpose)."""
    from arbius_tpu.node import factory

    cfg = MiningConfig(models=tuple(
        ModelConfig(id="0x" + f"{i:02x}" * 32, template=t, tiny=True)
        for i, t in enumerate(factory._BUILDERS)))
    contracts = factory.mesh_contracts(cfg)
    assert set(contracts) == set(factory._BUILDERS)
    assert all(getattr(mod, "MESH_LAYOUTS") for mod in contracts.values())


# -- dispatch-time placement ------------------------------------------------

def test_batch_specs_shard_when_divisible_else_replicate():
    mesh = meshsolve.boot_mesh({"dp": 2})
    spec, sharded = meshsolve.batch_specs(mesh, 4)
    assert sharded and spec(2).spec[0] == "dp"
    spec, sharded = meshsolve.batch_specs(mesh, 3)
    assert not sharded and spec(2).spec == ()


def test_estimate_and_record_collective_bytes():
    assert meshsolve.estimate_collective_bytes(None, (2, 8, 8), "f4") == {}
    mesh = meshsolve.boot_mesh({"dp": 2})
    est = meshsolve.estimate_collective_bytes(mesh, (2, 8, 8), np.float32)
    # each chip holds half the 512-byte output and receives the rest
    assert est == {"dp": 256}
    obs = Obs()
    with use_obs(obs):
        meshsolve.record_collective_bytes(est)
        meshsolve.record_collective_bytes(est)
    c = obs.registry.counter("arbius_collective_bytes_total",
                             labelnames=("axis",))
    assert c.value(axis="dp") == 512.0
    # no ambient obs: a no-op, never a crash (library code is node-free)
    meshsolve.record_collective_bytes(est)


def test_record_bucket_estimate_caches_and_skips_degraded_batch():
    """The hot-loop contract: the estimate is computed once per bucket
    (later dispatches reuse the cached dict), and a bucket that degraded
    to a replicated batch is not charged dp gathers that never cross
    chips."""
    mesh = meshsolve.boot_mesh({"dp": 2})
    cache: dict = {}
    obs = Obs()
    with use_obs(obs):
        # batch 3 does not divide dp=2: replicated batch, no dp traffic
        meshsolve.record_bucket_estimate(
            cache, 3, mesh, np.zeros((3, 8, 8), np.float32), 3)
        assert cache[3] == {}
        # batch 4 shards: half the 1024-byte output crosses chips
        out4 = np.zeros((4, 8, 8), np.float32)
        meshsolve.record_bucket_estimate(cache, 4, mesh, out4, 4)
        assert cache[4] == {"dp": 512}
        # second dispatch reuses the cache (poison it to prove reuse)
        cache[4] = {"dp": 7}
        meshsolve.record_bucket_estimate(cache, 4, mesh, out4, 4)
    c = obs.registry.counter("arbius_collective_bytes_total",
                             labelnames=("axis",))
    assert c.value(axis="dp") == 519.0  # 512 + the poisoned 7
    # mesh=None: no-op, caches nothing
    meshsolve.record_bucket_estimate(cache, 1, None,
                                     np.zeros((1,), np.float32), 1)
    assert 1 not in cache


def test_tp_estimate_counts_rule_sharded_params():
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = meshsolve.boot_mesh({"dp": 2, "tp": 2})
    params = {
        "qkv": jax.device_put(np.zeros((8, 8), np.float32),
                              NamedSharding(mesh, P(None, "tp"))),
        "norm": jax.device_put(np.zeros((8,), np.float32),
                               NamedSharding(mesh, P())),
    }
    est = meshsolve.estimate_collective_bytes(mesh, (2, 8, 8), np.float32,
                                              params=params)
    # ring allreduce term: 2·(tp-1)/tp of the 256-byte sharded slab;
    # the replicated norm leaf contributes nothing
    assert est["tp"] == 256


# -- byte equality across layouts (the acceptance gate) ---------------------

_TMPL = load_template("anythingv3")


def _items(n):
    return [(hydrate_input({"prompt": f"mesh task {i}",
                            "negative_prompt": ""}, _TMPL), 1000 + i)
            for i in range(n)]


def _cids(runner, canonical_batch):
    model = RegisteredModel(id="0x" + "11" * 32, template=_TMPL,
                            runner=runner)
    return [c for c, _ in solve_cid_batch(model, _items(5),
                                          canonical_batch=canonical_batch)]


@pytest.mark.parametrize("canonical_batch", [1, 4])
@pytest.mark.parametrize("probe_cls,layouts", [
    (meshsolve.ShardedImageProbe, ({"dp": 2}, {"dp": 2, "tp": 2})),
    (meshsolve.ShardedSeqProbe, ({"dp": 2}, {"dp": 2, "sp": 2})),
], ids=["image", "seq"])
def test_probe_cids_identical_at_every_layout(probe_cls, layouts,
                                              canonical_batch):
    """Same bucket at mesh-off, dp-only, and dp·tp (image) / dp·sp
    (video-shaped): byte-identical files ⇒ identical CIDs, through the
    REAL node solve path (bucketing, canonical-batch padding, chunk
    prefetch, gather). 5 items over canonical_batch 4 also exercises
    the padded under-filled final chunk."""
    base = _cids(probe_cls(mesh=None), canonical_batch)
    assert len(set(base)) == 5  # distinct inputs ⇒ distinct bytes
    for layout in layouts:
        mesh = meshsolve.boot_mesh(layout)
        assert _cids(probe_cls(mesh=mesh), canonical_batch) == base, layout


def test_seq_probe_underfilled_bucket_degrades_bitwise():
    """batch % dp != 0 cannot ride the shard_map (it hard-partitions
    the batch axis); the probe degrades that bucket to the single-device
    program whose bytes match by construction."""
    mesh = meshsolve.boot_mesh({"dp": 2, "sp": 2})
    base = _cids(meshsolve.ShardedSeqProbe(mesh=None), 3)
    assert _cids(meshsolve.ShardedSeqProbe(mesh=mesh), 3) == base


@pytest.mark.slow
@pytest.mark.model
def test_sd15_real_pipeline_dp2_bitwise_equal_to_mesh_off():
    """The real (tiny) SD-1.5 bucket program: dp-only sharding is a pure
    layout change — same XLA math per sample — so the generated images
    are BIT-identical to mesh-off. tp layouts are deliberately NOT
    asserted equal: reduction order moves, which is why each tp layout
    is its own golden-pinned determinism class."""
    from arbius_tpu.models.sd15 import SD15Config, SD15Pipeline
    from arbius_tpu.node.factory import tiny_byte_tokenizer

    cfg = SD15Config.tiny()
    kw = dict(width=64, height=64, num_inference_steps=2,
              scheduler="DDIM")
    out = {}
    for name, mesh in (("off", None),
                       ("dp2", meshsolve.boot_mesh({"dp": 2}))):
        p = SD15Pipeline(cfg, tokenizer=tiny_byte_tokenizer(cfg.text),
                        mesh=mesh)
        params = p.place_params(p.init_params(seed=0))
        out[name] = p.generate(params, ["a cat", "a dog"], ["", ""],
                               [11, 12], **kw)
    np.testing.assert_array_equal(out["off"], out["dp2"])


# -- per-layout goldens (the graphlint gate) --------------------------------

@pytest.mark.parametrize("batch", [4, 2])   # dp-sharded / replicated
def test_flash_kernel_lowers_for_tpu_inside_a_gspmd_mesh_program(
        monkeypatch, batch):
    """XLA cannot partition a Mosaic custom call: on the four-chip host
    the dp mesh bucket died at lowering ("Mosaic kernels cannot be
    automatically partitioned") — a path no CPU run reaches, because
    `attention` only picks the kernel on TPU. Cross-lower for TPU here:
    bare, the dispatch is refused; traced through `on_mesh` (what the
    sd15/kandinsky2 mesh buckets do) it lowers, whether the batch
    divides dp or stays replicated."""
    import jax
    import jax.numpy as jnp

    from arbius_tpu.ops import flash
    from arbius_tpu.parallel import build_mesh

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = build_mesh(MeshSpec(dp=4), devices=jax.devices()[:4])
    spec, _ = meshsolve.batch_specs(mesh, batch)
    qkv = (jax.ShapeDtypeStruct((batch, 2, 1024, 40), jnp.bfloat16),) * 3

    def lower(fn):
        jitted = jax.jit(fn, in_shardings=(spec(4),) * 3,
                         out_shardings=spec(4))
        return jitted.trace(*qkv).lower(
            lowering_platforms=("tpu",)).as_text()

    def attend(q, k, v):
        return flash.attention(q, k, v)

    with pytest.raises(NotImplementedError, match="automatically partitioned"):
        lower(attend)
    assert "tpu_custom_call" in lower(flash.on_mesh(attend, mesh))


def test_every_shipped_family_layout_pair_has_a_golden():
    """Each family publishes its shipped layouts as data (MESH_LAYOUTS);
    every (family, layout) pair must have a golden fingerprint under
    goldens/graph/ — the per-layout determinism classes are pinned, not
    implied."""
    from arbius_tpu.models import all_trace_specs

    by_model: dict[str, set[str]] = {}
    for s in all_trace_specs():
        by_model.setdefault(s.model, set()).add(s.mesh)
        assert (GOLDENS_DIR / f"{s.key}.json").exists(), s.key

    def tag(axes):
        return ".".join(f"{a}2" for a in axes)

    from arbius_tpu.models.kandinsky2 import pipeline as k2
    from arbius_tpu.models.sd15 import pipeline as sd15
    from arbius_tpu.models.video import pipeline as video

    for model, mod in (("anythingv3", sd15), ("kandinsky2", k2),
                       ("zeroscopev2xl", video)):
        for axes in mod.MESH_LAYOUTS:
            assert tag(axes) in by_model[model], (model, axes)
    assert {"dp2.tp2", "single"} <= by_model["meshprobe"]
    assert "dp2.sp2" in by_model["meshprobe"]


def test_seq_probe_noncanonical_psum_fires_graph403():
    """The GRAPH403 gate, pinned through a REAL meshsolve-shaped psum:
    the shipped seq probe reduces over the canonical single axis and
    audits clean; the same program built with a deliberately
    non-canonical multi-axis reduction order is a finding."""
    import jax
    import jax.numpy as jnp

    from arbius_tpu.analysis.graph import run_rules, trace_spec
    from arbius_tpu.models import TraceSpec

    mesh = abstract_mesh(MeshSpec(dp=2, sp=2))
    sds = jax.ShapeDtypeStruct
    args = (sds((8, 8), jnp.float32), sds((2,), jnp.uint32))

    def spec_for(fn, tag):
        return TraceSpec(model="synthetic", entry=f"seqprobe-{tag}",
                         bucket="b2.f4", mesh="dp2.sp2", dtype="float32",
                         build=lambda: (fn, args))

    good = meshsolve.build_seq_probe_fn(mesh, 4)
    assert not run_rules(trace_spec(spec_for(good, "canonical")))

    bad = meshsolve.build_seq_probe_fn(mesh, 4, psum_axes=("sp", "dp"))
    hits = run_rules(trace_spec(spec_for(bad, "reversed")))
    assert [f.rule for f in hits] == ["GRAPH403"]
    assert "canonical" in hits[0].message


# -- simnet under a mesh ----------------------------------------------------

def test_simnet_clean_and_crash_restart_hold_on_dp2_mesh(tmp_path):
    """The satellite's end-to-end gate: the full signed-tx miner
    lifecycle with REAL sharded XLA solves on the virtual dp2 mesh —
    SIM101-109 hold for `clean` and `crash-restart`, and every accepted
    CID matches the mesh-off run of the same probe (same seed, same
    fault schedule)."""
    from arbius_tpu.sim.harness import run_scenario
    from arbius_tpu.sim.invariants import check_all
    from arbius_tpu.sim.scenario import get_scenario

    def cids(r):
        return {"0x" + t.hex(): "0x" + s.cid.hex()
                for t, s in r.engine.solutions.items()}

    for name in ("clean", "crash-restart"):
        base = run_scenario(get_scenario(name), 1, mesh={},
                            db_path=str(tmp_path / f"{name}-off.sqlite"))
        meshed = run_scenario(get_scenario(name), 1, mesh={"dp": 2},
                              db_path=str(tmp_path / f"{name}-dp2.sqlite"))
        for r in (base, meshed):
            findings = check_all(r)
            assert not findings, (name, [f.text() for f in findings])
            assert r.quiescent
        assert cids(base) == cids(meshed) and cids(base), name
    assert meshed.restarts == 1  # the crash actually happened
