"""quantserve — int8/fp8 execution modes under the determinism gate.

The contracts under test (docs/quantization.md):

  * weight quantization is symmetric per-output-channel with f32
    scales; dequant passes through f32 (GRAPH407's beat) and the bf16
    mode is the pre-quant tree byte-for-byte (untouched).
  * the EQuARX-style quantized ring allreduce keeps every replica
    bit-identical, is deterministic run-to-run, and degrades to the
    plain psum at bf16.
  * `estimate_collective_bytes` reports actual wire bytes when the tp
    allreduce runs quantized (`wire_dtype` — the obs satellite).
  * a precision mode is a determinism class: own bucket keys, own cost
    rows (sqlite migration included), own AOT cache keys, own CIDs —
    and dp-sharding stays byte-identical WITHIN a mode.
  * simnet clean + crash-restart hold every SIM invariant at int8.
"""
import json
import pathlib
import sqlite3

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from arbius_tpu import quant
from arbius_tpu.node.config import (
    ConfigError,
    MiningConfig,
    ModelConfig,
    PrecisionConfig,
    load_config,
)

REPO = pathlib.Path(__file__).resolve().parent.parent


# -- quant core -------------------------------------------------------------

@pytest.mark.parametrize("mode", ["int8", "fp8"])
def test_quantize_round_trip_and_scale_contract(mode):
    w = np.random.RandomState(0).randn(16, 8).astype(np.float32)
    q = quant.quantize_leaf(w, mode)
    assert quant.is_quantized_leaf(q)
    assert q["qs"].dtype == jnp.float32          # scales are f32, always
    assert q["qs"].shape == (8,)                 # per-OUTPUT-channel
    assert q["qv"].dtype == quant.storage_dtype(mode)
    back = np.asarray(quant.dequantize_leaf(q))
    assert back.dtype == np.float32
    # error envelope: int8's grid step is absmax/127 per channel; fp8
    # e4m3 rounds RELATIVE (3 mantissa bits → one part in 16)
    bound = quant.INT8_BOUND if mode == "int8" else quant.FP8_BOUND
    step = np.abs(w).max(axis=0) / bound
    assert np.all(np.abs(back - w) <=
                  np.maximum(1.001 * step, np.abs(w) / 16.0))


def test_quantize_tree_eligibility_and_bf16_identity():
    tree = {"layer": {"kernel": jnp.ones((4, 4)), "bias": jnp.ones((4,)),
                      "scale": jnp.ones((4,))},
            "ids": jnp.arange(4)}                # integer leaf: untouched
    assert quant.quantize_tree(tree, "bf16") is tree  # byte-identical path
    qt = quant.quantize_tree(tree, "int8")
    assert quant.is_quantized_leaf(qt["layer"]["kernel"])
    # 0/1-D leaves and integer leaves stay full-width
    assert qt["layer"]["bias"].dtype == jnp.float32
    assert qt["ids"].dtype == tree["ids"].dtype
    back = quant.dequantize_tree(qt)
    assert jax.tree_util.tree_structure(back) == \
        jax.tree_util.tree_structure(tree)
    # dequantize_tree is a no-op on an unquantized tree
    assert quant.dequantize_tree(tree)["layer"]["bias"] is \
        tree["layer"]["bias"]


def test_abstract_quantized_matches_concrete_structure():
    tree = {"k": jnp.ones((8, 4))}
    concrete = quant.quantize_tree(tree, "int8")
    abstract = quant.abstract_quantized(jax.eval_shape(lambda: tree),
                                        "int8")
    assert jax.tree_util.tree_structure(abstract) == \
        jax.tree_util.tree_structure(concrete)
    assert abstract["k"]["qv"].dtype == concrete["k"]["qv"].dtype
    assert abstract["k"]["qs"].shape == concrete["k"]["qs"].shape


def test_validate_mode_one_sentence_error():
    with pytest.raises(ValueError) as e:
        quant.validate_mode("int4", where="precision.default")
    assert "precision.default" in str(e.value)
    assert "int8" in str(e.value)
    assert quant.mode_tag("bf16") == ""          # pre-quant tags unchanged
    assert quant.mode_tag("int8") == ".int8"


def test_quantized_dot_accumulates_wide():
    qx = jnp.full((2, 4), 100, jnp.int8)
    qw = jnp.full((4, 2), 100, jnp.int8)
    out = quant.quantized_dot(qx, qw, jnp.ones((2,)), jnp.ones((2,)),
                              "int8")
    # 4 * 100 * 100 = 40000 wraps in int8 — int32 accumulation doesn't
    assert out.dtype == jnp.float32
    assert float(out[0, 0]) == 40000.0
    with pytest.raises(ValueError):
        quant.quantized_dot(qx, qw, jnp.ones((2,)), jnp.ones((2,)),
                            "bf16")


# -- quantized ring allreduce ----------------------------------------------

def _ring_allreduce(x, tp, mode):
    from jax.experimental.shard_map import shard_map
    from jax.sharding import PartitionSpec as P

    from arbius_tpu.parallel.collectives import quantized_ring_allreduce
    from arbius_tpu.parallel.mesh import MeshSpec, build_mesh

    mesh = build_mesh(MeshSpec(tp=tp), devices=jax.devices()[:tp])
    fn = jax.jit(shard_map(
        lambda xs: quantized_ring_allreduce(xs, "tp", mode=mode),
        mesh=mesh, in_specs=P("tp"), out_specs=P("tp"), check_rep=False))
    return np.asarray(fn(x))


@pytest.mark.parametrize("tp", [2, 4])
def test_quantized_ring_allreduce_replicas_identical_and_accurate(tp):
    x = np.random.RandomState(1).randn(tp, 6, 5).astype(np.float32)
    ref = x.sum(axis=0)
    out = _ring_allreduce(x, tp, "int8")
    # every replica bit-identical — a diverged replica forks CIDs
    for i in range(1, tp):
        assert np.array_equal(out[i], out[0])
    # deterministic run-to-run (fixed ring schedule)
    assert np.array_equal(out, _ring_allreduce(x, tp, "int8"))
    # int8 wire error well under bf16's own mantissa step at this range
    assert np.max(np.abs(out[0] - ref)) < 0.05 * np.max(np.abs(ref))


def test_quantized_ring_allreduce_bf16_degrades_to_psum():
    x = np.random.RandomState(2).randn(2, 4, 3).astype(np.float32)
    out = _ring_allreduce(x, 2, "bf16")
    assert np.allclose(out[0], x.sum(axis=0), atol=1e-5)


# -- wire-byte accounting (obs satellite) -----------------------------------

def test_estimate_collective_bytes_wire_dtype_override():
    from arbius_tpu.parallel.mesh import MeshSpec, build_mesh
    from arbius_tpu.parallel.meshsolve import estimate_collective_bytes
    from arbius_tpu.parallel.sharding import shard_params

    mesh = build_mesh(MeshSpec(dp=2, tp=2), devices=jax.devices()[:4])
    params = {"w": jnp.ones((8, 8), jnp.float32)}
    placed = shard_params(params, mesh,
                          ((r".*w$", __import__("jax").sharding
                            .PartitionSpec(None, "tp")),))
    full = estimate_collective_bytes(mesh, (2, 8, 8), np.float32,
                                     params=placed)
    wired = estimate_collective_bytes(mesh, (2, 8, 8), np.float32,
                                      params=placed,
                                      wire_dtype=np.int8)
    # tp term: 2·(tp-1)/tp · elements · width — 4-byte vs 1-byte wire
    assert full["tp"] == 2 * 8 * 8 * 4 * 1 // 2
    assert wired["tp"] == 2 * 8 * 8 * 1 * 1 // 2
    assert wired["tp"] * 4 == full["tp"]
    # the dp output-gather term is untouched by the tp wire override
    assert full["dp"] == wired["dp"]


def test_quantized_probe_reports_quantized_tp_wire_bytes():
    """The int8 img probe's tp slab is 1-byte on the wire — the metered
    estimate must come out strictly below the bf16 probe's."""
    from arbius_tpu.parallel.mesh import MeshSpec, build_mesh
    from arbius_tpu.parallel.meshsolve import ShardedImageProbe
    from arbius_tpu.obs import Obs, use_obs

    def tp_bytes(mode):
        mesh = build_mesh(MeshSpec(dp=2, tp=2), devices=jax.devices()[:4])
        obs = Obs(journal_capacity=16)
        with use_obs(obs):
            probe = ShardedImageProbe(mesh=mesh, mode=mode)
            probe.run_batch([({"prompt": f"t{i}"}, i) for i in range(2)])
        c = obs.registry.counter("arbius_collective_bytes_total",
                                 labelnames=("axis",))
        return c.value(axis="tp")

    assert 0 < tp_bytes("int8") < tp_bytes("bf16")


# -- precision config -------------------------------------------------------

def test_precision_config_validation_is_one_sentence():
    with pytest.raises(ConfigError) as e:
        load_config('{"precision": {"default": "fp4"}}')
    assert "fp4" in str(e.value)
    with pytest.raises(ConfigError):
        load_config('{"precision": {"templates": {"anythingv3": "x"}}}')
    with pytest.raises(ConfigError):
        load_config('{"precision": {"templates": ["int8"]}}')
    cfg = load_config('{"precision": {"default": "int8", '
                      '"templates": {"kandinsky2": "bf16"}}}')
    assert cfg.precision.mode_for("anythingv3") == "int8"
    assert cfg.precision.mode_for("kandinsky2") == "bf16"
    # the default default is the pre-quant node
    assert MiningConfig().precision.mode_for("anythingv3") == "bf16"


def test_example_config_ships_precision_block():
    raw = (REPO / "MiningConfig.example.json").read_text()
    cfg = load_config(raw)
    assert cfg.precision.default == "bf16"
    assert json.loads(raw)["precision"]["default"] == "bf16"


def test_rvm_rejects_quantized_modes_at_boot():
    from arbius_tpu.node.factory import build_registry

    cfg = MiningConfig(
        models=(ModelConfig(id="0x" + "22" * 32,
                            template="robust_video_matting", tiny=True,
                            golden={"input": {}, "seed": 0, "cid": "0x0",
                                    "probe_video": "2x16x16"}),),
        precision=PrecisionConfig(default="int8"),
        compile_cache=False)
    with pytest.raises(ConfigError) as e:
        build_registry(cfg)
    assert "robust_video_matting" in str(e.value)


# -- mode is a bucket/cost identity -----------------------------------------

def test_bucket_key_carries_mode():
    from arbius_tpu.node.solver import bucket_key, bucket_mode

    h = {"width": 64, "height": 64, "num_inference_steps": 2,
         "scheduler": "DDIM"}
    k_bf = bucket_key("0xmm", h)
    k_q = bucket_key("0xmm", h, "int8")
    assert k_bf != k_q
    assert bucket_mode(k_bf) == "bf16"
    assert bucket_mode(k_q) == "int8"
    # pre-quant 6-tuples (persisted keys, old tests) read as bf16
    assert bucket_mode(k_bf[:6]) == "bf16"
    from arbius_tpu.node.costmodel import bucket_str

    assert bucket_str(k_bf) == bucket_str(k_q)  # shape part, mode aside


def test_cost_model_db_migration_preserves_rows_and_separates_modes(
        tmp_path):
    """A pre-quant `cost_model` table migrates in place: old rows stamp
    mode='bf16', and rows at a second mode can then coexist (the old
    3-column primary key could not hold both)."""
    from arbius_tpu.node.db import NodeDB

    path = str(tmp_path / "old.sqlite")
    conn = sqlite3.connect(path)
    conn.executescript("""
        CREATE TABLE cost_model (
            model TEXT, bucket TEXT, layout TEXT,
            chip_seconds REAL, samples INT, updated INT,
            PRIMARY KEY (model, bucket, layout));
        INSERT INTO cost_model VALUES
            ('0xaa', '64x64.s2.DDIM.f-', 'single', 3.5, 9, 77);
    """)
    conn.commit()
    conn.close()
    db = NodeDB(path)
    rows = db.load_cost_rows()
    assert rows == [("0xaa", "64x64.s2.DDIM.f-", "single", "bf16",
                     3.5, 9, 77)]
    db.upsert_cost_rows([("0xaa", "64x64.s2.DDIM.f-", "single", "int8",
                          1.5, 4, 88)])
    both = db.load_cost_rows()
    assert len(both) == 2 and {r[3] for r in both} == {"bf16", "int8"}
    db.close()
    # idempotent: reopening an already-migrated file is a no-op
    db2 = NodeDB(path)
    assert len(db2.load_cost_rows()) == 2
    db2.close()


# -- per-mode program identity (AOT keys, CIDs) -----------------------------

def test_bf16_and_int8_programs_hash_to_different_aot_keys():
    """The coldboot satellite: cross-mode executable poisoning is
    structurally impossible — the graphlint fingerprint differs, so the
    derived cache key differs even with identical env and args."""
    from arbius_tpu.aotcache import env_signature
    from arbius_tpu.aotcache.store import derive_key
    from arbius_tpu.analysis.graph.fingerprint import fingerprint
    from arbius_tpu.parallel.meshsolve import (
        _PROBE_DIM,
        ShardedImageProbe,
    )

    env = env_signature()
    fps = {}
    for mode in ("bf16", "int8"):
        probe = ShardedImageProbe(mode=mode)
        p = jax.ShapeDtypeStruct((_PROBE_DIM, _PROBE_DIM), jnp.float32)
        if mode != "bf16":
            p = quant.abstract_quantized(p, mode)
        fps[mode] = fingerprint(jax.make_jaxpr(probe._fn(1))(
            p, jax.ShapeDtypeStruct((1,), jnp.uint32)))
    assert fps["bf16"] != fps["int8"]
    assert derive_key(fps["bf16"], env, "sig") != \
        derive_key(fps["int8"], env, "sig")


def test_probe_int8_layout_invariance_and_mode_separation():
    """Within int8: mesh-off == dp2 byte-identical (dp shards samples;
    the quantized weights are replicated identical bits). Across modes:
    different bytes — a mode is its own determinism class."""
    from arbius_tpu.parallel.mesh import MeshSpec, build_mesh
    from arbius_tpu.parallel.meshsolve import ShardedImageProbe

    items = [({"prompt": f"t{i}"}, 1000 + i) for i in range(4)]

    def run(mesh_cfg, mode):
        mesh = None
        if mesh_cfg:
            n = int(np.prod(list(mesh_cfg.values())))
            mesh = build_mesh(MeshSpec(**mesh_cfg),
                              devices=jax.devices()[:n])
        probe = ShardedImageProbe(mesh=mesh, mode=mode)
        return [f["out-1.png"] for f in probe.run_batch(items)]

    off = run(None, "int8")
    assert off == run({"dp": 2}, "int8")
    assert off == run({"dp": 2, "tp": 2}, "int8")  # concat-only tp
    assert off != run(None, "bf16")
    assert off != run(None, "fp8")


def test_seq_probe_quantized_allreduce_is_deterministic():
    """The dp2.sp2 int8 seq probe carries a REAL quantized ring
    allreduce (its golden pins the program) — run-to-run byte
    equality is the determinism claim for the quantized collective."""
    from arbius_tpu.parallel.mesh import MeshSpec, build_mesh
    from arbius_tpu.parallel.meshsolve import ShardedSeqProbe

    items = [({"prompt": "a"}, 1), ({"prompt": "b"}, 2)]

    def run():
        mesh = build_mesh(MeshSpec(dp=2, sp=2), devices=jax.devices()[:4])
        probe = ShardedSeqProbe(mesh=mesh, mode="int8")
        return [f["out-1.png"] for f in probe.run_batch(items)]

    assert run() == run()


# -- simnet at int8 (acceptance) --------------------------------------------

def test_simnet_clean_green_at_int8_and_pipeline_invariant():
    """SIM101-112 hold at int8, and pipeline on/off reach identical
    CIDs within the mode — the schedule still never touches bytes."""
    from arbius_tpu.sim.harness import run_scenario
    from arbius_tpu.sim.invariants import check_all
    from arbius_tpu.sim.scenario import get_scenario

    clean = get_scenario("clean").with_tasks(4)
    on = run_scenario(clean, 3, mesh={}, precision="int8")
    findings = check_all(on)
    assert not findings, "\n".join(f.text() for f in findings)
    off = run_scenario(clean, 3, mesh={}, precision="int8",
                       pipeline=False)
    assert not check_all(off)
    cids = lambda r: {t: s.cid for t, s in r.engine.solutions.items()}
    assert cids(on) == cids(off)
    # and the mode really ran: bf16 CIDs differ
    bf = run_scenario(clean, 3, mesh={}, precision="bf16")
    assert cids(on) != cids(bf)


def test_simnet_crash_restart_green_at_int8(tmp_path):
    from arbius_tpu.sim.harness import run_scenario
    from arbius_tpu.sim.invariants import check_all
    from arbius_tpu.sim.scenario import get_scenario

    res = run_scenario(get_scenario("crash-restart"), 5, mesh={},
                       precision="int8",
                       db_path=str(tmp_path / "sim.sqlite"))
    findings = check_all(res)
    assert not findings, "\n".join(f.text() for f in findings)
    assert res.quiescent
