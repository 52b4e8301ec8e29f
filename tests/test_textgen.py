"""textgen tier-1 suite (docs/text-serving.md): the jitted KV-cache
decode loop's determinism contract (same inputs → same tokens; the
decode EDGE is prefix-stable, the prompt edge is consensus config),
the sequence-aware bucket key (9-tuples extend, 6/7-tuple legacy keys
parse byte for byte), ragged-bucket chunk padding, the validated
`textgen` config block, the costmodel render cap, the decode_stall
healthwatch rule, the text-stream simnet scenario under SIM101-113,
and the e2e CID matrix through a real MinerNode (pipeline on/off ×
AOT off/cold/warm × mesh-off/dp2)."""
from __future__ import annotations

import json
import os
import sys

import jax
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))

from arbius_tpu.models.textgen import (
    TextGenConfig,
    TextGenPipeline,
    tokens_to_bytes,
)
from arbius_tpu.node.config import ConfigError, TextgenConfig, load_config
from arbius_tpu.node.costmodel import bucket_str
from arbius_tpu.node.solver import (
    TextGenRunner,
    bucket_key,
    bucket_mode,
    chunk_items,
    count_decode_stall,
)

REPO = os.path.join(os.path.dirname(__file__), "..")

# tiny trace-speed bucket edges: 8+4 positions out of tiny()'s 96
P_EDGES = (8, 16)
T_EDGES = (4, 8)


@pytest.fixture(scope="module")
def pipe():
    return TextGenPipeline(TextGenConfig.tiny(), prompt_buckets=P_EDGES,
                           decode_buckets=T_EDGES, top_k=4)


@pytest.fixture(scope="module")
def params(pipe):
    return pipe.init_params(seed=0)


# -- the decode loop's determinism contract ---------------------------------

def test_generate_is_deterministic_per_sampler(pipe, params):
    for sampler in ("greedy", "top_k"):
        a = pipe.generate(params, ["hi"], [1234], prompt_bucket=8,
                          decode_bucket=4, sampler=sampler)
        b = pipe.generate(params, ["hi"], [1234], prompt_bucket=8,
                          decode_bucket=4, sampler=sampler)
        assert np.array_equal(a, b), f"{sampler} tokens drifted"
        assert a.shape == (1, 4) and a.dtype == np.int32


def test_decode_edge_is_prefix_stable(pipe, params):
    """The load-bearing claim of docs/text-serving.md: the decode
    bucket edge is NOT bytes-affecting. A longer decode bucket's first
    T tokens are bit-identical to the shorter bucket's output, for both
    samplers — so host-side truncation to the requested budget is sound
    and decode edges are free per-node config."""
    for sampler in ("greedy", "top_k"):
        short = pipe.generate(params, ["prefix check"], [7],
                              prompt_bucket=16, decode_bucket=4,
                              sampler=sampler)
        long = pipe.generate(params, ["prefix check"], [7],
                             prompt_bucket=16, decode_bucket=8,
                             sampler=sampler)
        assert np.array_equal(short[0], long[0, :4]), \
            f"{sampler}: decode edge changed the shared prefix"


def test_top_k_threads_the_task_seed(pipe, params):
    """Two task seeds must be able to sample different tokens (the
    seed is an INPUT to one compiled program, docs/text-serving.md);
    greedy ignores the seed entirely."""
    a = pipe.generate(params, ["seed check"], [1], prompt_bucket=16,
                      decode_bucket=8, sampler="top_k")
    b = pipe.generate(params, ["seed check"], [2], prompt_bucket=16,
                      decode_bucket=8, sampler="top_k")
    assert not np.array_equal(a, b), \
        "top_k sampled identically under different seeds"
    g1 = pipe.generate(params, ["seed check"], [1], prompt_bucket=16,
                       decode_bucket=8, sampler="greedy")
    g2 = pipe.generate(params, ["seed check"], [2], prompt_bucket=16,
                       decode_bucket=8, sampler="greedy")
    assert np.array_equal(g1, g2), "greedy must be seed-free"


def test_bucket_policy_smallest_edge_that_fits(pipe):
    # "hi" needs 2+2=4 bytes+specials → first edge 8
    assert pipe.prompt_bucket_for("hi") == 8
    # 7 bytes + 2 → 9 > 8 → next edge
    assert pipe.prompt_bucket_for("seven77") == 16
    # over-long prompts clamp to the top edge (tokenizer truncation)
    assert pipe.prompt_bucket_for("x" * 100) == 16
    assert pipe.decode_bucket_for(1) == 4
    assert pipe.decode_bucket_for(5) == 8
    assert pipe.decode_bucket_for(999) == 8  # clamped; config caps it


def test_tokens_to_bytes_total_over_model_vocab():
    # stops at the first eos, drops non-byte ids, honors the limit
    ids = [104, 105, 300, 33, 258, 104]
    assert tokens_to_bytes(ids, 6) == b"hi!"
    assert tokens_to_bytes(ids, 2) == b"hi"
    assert tokens_to_bytes([258, 104], 2) == b""
    assert tokens_to_bytes([511, 257], 2) == b""  # nothing representable


def test_trace_specs_cover_prefill_decode_and_generate():
    from arbius_tpu.models.trace_specs import all_trace_specs

    specs = [s for s in all_trace_specs() if s.model == "textgen"]
    entries = sorted({s.entry for s in specs})
    assert entries == ["decode", "generate", "prefill"]
    assert len(specs) == 6
    # both samplers goldened as separate decode classes
    assert {s.bucket for s in specs if s.entry == "decode"} == \
        {"b1.p8.t4.greedy", "b1.p8.t4.top_k"}


# -- bucket key: 9-tuple extension, legacy parse (satellite) ----------------

def test_bucket_key_legacy_shapes_unchanged():
    img = {"width": 512, "height": 512, "num_inference_steps": 20,
           "scheduler": "DDIM"}
    key = bucket_key("0xabc", img)
    assert key == ("0xabc", 512, 512, 20, "DDIM", None, "bf16")
    assert len(key) == 7
    assert bucket_mode(key) == "bf16"
    # pre-quant 6-tuples (persisted rows) still read as bf16
    assert bucket_mode(key[:6]) == "bf16"
    assert bucket_str(key) == "512x512.s20.DDIM.f-"
    assert bucket_str(key[:6]) == "512x512.s20.DDIM.f-"


def test_bucket_key_text_9_tuple_and_sampler_slot():
    hyd = {"prompt": "hi", "sampler": "top_k", "max_new_tokens": 8,
           "_prompt_bucket": 32, "_decode_bucket": 16}
    key = bucket_key("0xdef", hyd, mode="int8")
    assert key == ("0xdef", None, None, None, "top_k", None, "int8",
                   32, 16)
    assert bucket_mode(key) == "int8"
    assert bucket_str(key) == "-x-.s-.top_k.f-.p32.t16"
    # without the injected fields the SAME hydrated input stays 7-wide
    bare = {k: v for k, v in hyd.items() if not k.startswith("_")}
    assert len(bucket_key("0xdef", bare)) == 7


def test_runner_prepare_hydrated_stamps_buckets(pipe, params):
    r = TextGenRunner(pipe, params)
    h = r.prepare_hydrated({"prompt": "hi", "max_new_tokens": 5})
    assert (h["_prompt_bucket"], h["_decode_bucket"]) == (8, 8)
    # pure function of (input, config): idempotent and input untouched
    assert r.prepare_hydrated(h) == h
    assert "_prompt_bucket" not in {"prompt": "hi"}


def test_text_bucket_span_carries_the_attention_kernels_counts(pipe, params):
    """`text.bucket` (docs/observability.md) states what a Pallas prefill
    attention kernel serves of the bucket: this family has none, so the
    three counts are there and read 0, as trinity's do off the TPU."""
    from arbius_tpu.obs import Obs, use_obs

    assert pipe.attn_kernel(2, 8) == (0, 0, 0)
    r = TextGenRunner(pipe, params)
    obs = Obs()
    h = r.prepare_hydrated({"prompt": "hi", "max_new_tokens": 4})
    with use_obs(obs):
        r.dispatch([(h, 1), (h, 2)])
    (ev,) = [e for e in obs.journal.events()
             if e.get("kind") == "span" and e["name"] == "text.bucket"]
    a = ev["attrs"]
    assert (a["batch"], a["prompt_bucket"], a["decode_bucket"]) == (2, 8, 4)
    assert a["kv_rows"] == a["kv_rows_full"]
    assert (a["attn_kernel_calls"], a["attn_blocks"],
            a["attn_blocks_dense"]) == (0, 0, 0)


def test_chunk_items_ragged_bucket_padding():
    items = [({"i": n}, n) for n in range(5)]
    chunks = chunk_items(items, 2)
    assert [(len(c), real) for c, real in chunks] == [(2, 2), (2, 2),
                                                      (2, 1)]
    # the ragged tail pads by REPEATING its last real item, never by
    # inventing one — the padded twin's bytes are discarded by n_real
    tail, real = chunks[-1]
    assert tail == [({"i": 4}, 4), ({"i": 4}, 4)] and real == 1
    # batch larger than the bucket: one chunk, fully padded
    (only,) = chunk_items(items[:1], 4)
    assert only == ([({"i": 0}, 0)] * 4, 1)


def test_cold_sequence_buckets_price_token_linearly():
    """node/sched.py static_seq (docs/scheduler.md): a cold 9-tuple
    prices at the static estimate scaled by its token count — ordering
    only, but a 96-token bucket must not price like a 20-token one."""
    from arbius_tpu.node.sched import CostSched

    class _Model:
        def predict(self, *a):
            return None

    class _Node:
        costmodel = _Model()
        solve_layout = "single"

        def _static_solve_seconds(self):
            return 10.0

    sched = CostSched.__new__(CostSched)
    sched.node = _Node()
    seq = ("m", None, None, None, "greedy", None, "bf16", 32, 16)
    assert sched._predict(seq, 1) == (10.0 * 48 / 64, "static_seq")
    legacy = ("m", 512, 512, 20, "DDIM", None, "bf16")
    assert sched._predict(legacy, 1) == (10.0, "static")


# -- config block (satellite) -----------------------------------------------

def test_textgen_config_validation_messages():
    with pytest.raises(ConfigError, match="ascending"):
        TextgenConfig(prompt_buckets=(32, 16))
    with pytest.raises(ConfigError, match="non-empty"):
        TextgenConfig(decode_buckets=())
    with pytest.raises(ConfigError, match=">= 3"):
        TextgenConfig(prompt_buckets=(2, 32))
    with pytest.raises(ConfigError, match="unmineable"):
        TextgenConfig(decode_buckets=(4, 8), max_new_tokens=9)
    with pytest.raises(ConfigError, match="top_k"):
        TextgenConfig(top_k=0)
    with pytest.raises(ConfigError, match="max_new_tokens"):
        TextgenConfig(max_new_tokens=0)


def test_example_config_carries_the_textgen_block():
    with open(os.path.join(REPO, "MiningConfig.example.json")) as f:
        cfg = load_config(f.read())
    assert cfg.textgen.prompt_buckets == (32, 64)
    assert cfg.textgen.decode_buckets == (16, 32)
    assert cfg.textgen.max_new_tokens == 32
    assert cfg.textgen.top_k == 8
    assert any(m.template == "textgen" for m in cfg.models)


def test_unknown_textgen_key_is_one_sentence():
    base = {"db_path": "x", "textgen": {"bogus": 1}}
    with pytest.raises(ConfigError, match="textgen"):
        load_config(json.dumps(base))


# -- costmodel render cap (satellite) ---------------------------------------

def test_render_rows_caps_with_explicit_omission_line():
    from costmodel import RENDER_CAP, render_rows

    def row(i):
        return {"model": f"m{i:03d}", "bucket": f"b{i}", "layout":
                "single", "mode": "bf16", "chip_seconds": 1.0,
                "samples": 2, "updated": 3}

    out = render_rows([row(i) for i in range(RENDER_CAP + 6)])
    lines = out.splitlines()
    assert lines[-1] == "(6 more buckets)"
    assert len(lines) == 1 + RENDER_CAP + 1  # header + cap + trailer
    # at or under the cap: no trailer, historic table byte for byte
    under = render_rows([row(i) for i in range(RENDER_CAP)])
    assert "more buckets" not in under
    assert len(under.splitlines()) == 1 + RENDER_CAP


# -- decode_stall healthwatch rule ------------------------------------------

class _FakeChain:
    now = 0

    def get_blocktime(self):
        return self.now


class _FakeDB:
    due: list = []

    def get_jobs(self, now, limit=None):
        return self.due[:limit]


class _FakeNode:
    def __init__(self, obs):
        self.obs = obs
        self.chain = _FakeChain()
        self.db = _FakeDB()
        self.task_feed = None


def test_decode_stall_rule_fires_on_counter_delta():
    from arbius_tpu.node.config import AlertsConfig
    from arbius_tpu.obs import Obs, use_obs
    from arbius_tpu.obs.healthwatch import RULE_NAMES, HealthWatch

    assert "decode_stall" in RULE_NAMES
    obs = Obs()
    hw = HealthWatch(obs, AlertsConfig(enabled=True))
    node = _FakeNode(obs)
    hw.evaluate(node)
    assert hw.states()["decode_stall"] == "ok"
    # the production counter site (TextGenRunner.finalize and the sim
    # decode gate both call this ONE function)
    with use_obs(obs):
        count_decode_stall(2)
    node.chain.now = 5
    hw.evaluate(node)
    assert hw.states()["decode_stall"] == "firing"  # instant rule
    node.chain.now = 10
    hw.evaluate(node)  # no new stalls → resolves
    assert hw.states()["decode_stall"] == "resolved"
    (ev, _) = obs.journal.events(kind="alert_transition")
    assert ev["alert"] == "decode_stall"
    assert "zero-byte" in ev["detail"]


# -- text-stream simnet scenario (SIM101-113) -------------------------------

def test_text_stream_scenario_holds_all_invariants(tmp_path):
    """The text-stream flood (docs/fault-injection.md): FaultyTextRunner
    under decode-stall + slow-runner + latency faults. Every SIM
    invariant must hold, the injected decode_stall faults must raise
    the mapped healthwatch alert (SIM113 required direction), and the
    fault draws must never touch output bytes — same seed, same CIDs,
    faults on or off by construction."""
    from arbius_tpu.sim.harness import run_scenario
    from arbius_tpu.sim.invariants import check_all, classify_tasks
    from arbius_tpu.sim.scenario import SCENARIOS, get_scenario

    assert "text-stream" in SCENARIOS
    result = run_scenario(get_scenario("text-stream"), 7,
                          db_path=str(tmp_path / "text.sqlite"),
                          healthwatch=True)
    findings = check_all(result)
    assert findings == [], [f"{f.rule}: {f.message}" for f in findings]
    assert set(classify_tasks(result).values()) == {"claimed"}
    stalls = result.plane.fault_counts.get("decode_stall", 0)
    assert stalls > 0, "scenario must actually inject decode stalls"
    raised = {e["alert"] for e in result.journal_events
              if e.get("kind") == "alert_transition"}
    assert "decode_stall" in raised


def test_decode_stall_fault_is_in_the_coverage_map():
    from arbius_tpu.sim.invariants import FAULT_ALERTS

    assert FAULT_ALERTS["decode_stall"] == ("decode_stall",)


# -- e2e: the CID equality matrix through a real MinerNode ------------------

def _text_world(pipe, params, *, canonical_batch=2, pipeline_on=False,
                aot_dir=None, template="textgen"):
    from arbius_tpu.chain import WAD, Engine, TokenLedger
    from arbius_tpu.node import (
        LocalChain,
        MinerNode,
        MiningConfig,
        ModelConfig,
        ModelRegistry,
        RegisteredModel,
    )
    from arbius_tpu.node.config import AotCacheConfig, PipelineConfig
    from arbius_tpu.templates.engine import load_template

    tok = TokenLedger()
    eng = Engine(tok, start_time=10_000)
    tok.mint(Engine.ADDRESS, 600_000 * WAD)
    miner, user = "0x" + "aa" * 20, "0x" + "01" * 20
    for a in (miner, user):
        tok.mint(a, 10**6 * WAD)
        tok.approve(a, Engine.ADDRESS, 10**30)
    mid = "0x" + eng.register_model(user, user, 0, b'{"f":"T"}').hex()
    registry = ModelRegistry()
    registry.register(RegisteredModel(
        id=mid, template=load_template(template),
        runner=TextGenRunner(pipe, params)))
    chain = LocalChain(eng, miner)
    chain.validator_deposit(100 * WAD)
    node = MinerNode(
        chain,
        MiningConfig(models=(ModelConfig(id=mid, template=template),),
                     canonical_batch=canonical_batch,
                     compile_cache=False,
                     pipeline=PipelineConfig(enabled=pipeline_on),
                     aot_cache=AotCacheConfig(enabled=True, dir=aot_dir)
                     if aot_dir else AotCacheConfig()),
        registry)
    node.boot(skip_self_test=True)
    return eng, node, mid, user


def _drive(eng, node, mid, user):
    """Submit 4 tasks (both samplers, two budgets inside one decode
    bucket) and tick to quiescence; returns {taskid: cid}."""
    while node.tick():
        pass
    for i in range(4):
        obj = {"prompt": f"matrix task {i}",
               "max_new_tokens": (3, 4)[i % 2],
               "sampler": ("greedy", "top_k")[i % 2]}
        eng.submit_task(user, 0, user, bytes.fromhex(mid[2:]),
                        (1 + i) * 10**18, json.dumps(
                            obj, sort_keys=True).encode())
    for _ in range(128):
        if node.tick() == 0:
            break
    cids = {"0x" + t.hex(): "0x" + s.cid.hex()
            for t, s in eng.solutions.items()}
    node.close()
    return cids


def test_e2e_cid_matrix_pipeline_aot_mesh(tmp_path):
    """The acceptance matrix (docs/text-serving.md): a text task solves
    end to end through MinerNode with byte-identical CIDs across
    pipeline on/off × AOT off/cold/warm × mesh-off/dp2. Every world
    builds a FRESH pipeline instance (fresh executable cache) over the
    same params, so the AOT warm world genuinely deserializes."""
    from arbius_tpu.parallel import MeshSpec, build_mesh

    cfg = TextGenConfig.tiny()

    def fresh_pipe(mesh=None):
        return TextGenPipeline(cfg, mesh=mesh, prompt_buckets=P_EDGES,
                               decode_buckets=T_EDGES, top_k=4)

    params = fresh_pipe().init_params(seed=0)
    aot = str(tmp_path / "aot")

    def world(label, **kw):
        mesh = kw.pop("mesh", None)
        p = fresh_pipe(mesh)
        pl = p.place_params(params) if mesh is not None else params
        cids = _drive(*_text_world(p, pl, **kw))
        assert len(cids) == 4, f"{label}: {len(cids)}/4 solved"
        return cids

    base = world("baseline")
    assert world("pipeline-on", pipeline_on=True) == base
    assert world("aot-cold", aot_dir=aot) == base
    assert world("aot-warm", aot_dir=aot) == base
    mesh = build_mesh(MeshSpec(dp=2), devices=jax.devices()[:2])
    assert world("dp2", mesh=mesh) == base


def test_empty_decode_counts_stall_but_still_commits(pipe, params):
    """A zero-byte answer is a VALID solve (docs/text-serving.md):
    finalize counts arbius_decode_stalls_total and returns the empty
    artifact unchanged — never a retry, never a mutation."""
    from arbius_tpu.obs import Obs, use_obs

    r = TextGenRunner(pipe, params)
    obs = Obs()
    # drive finalize directly with tokens that detokenize to nothing
    tokens = np.full((1, 4), pipe.EOS_ID, np.int32)
    with use_obs(obs):
        out = r.finalize((tokens, [4]), 1)
    assert out == [{"out-1.txt": b""}]
    assert obs.registry.counter(
        "arbius_decode_stalls_total").value() == 1
