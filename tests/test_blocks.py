"""Named blocks of the bucket programs (arbius_tpu/obs/blocks.py,
docs/observability.md "Blocks"): the compiled HLO carries each scope on
its operations' op_name paths, `block_map` reads them back, every
family's bucket program carries its whole vocabulary, the obs builds a
map only when asked and from the dispatch's own executable, and each
`solve.dispatch` names the program its chunk ran."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

from arbius_tpu.obs import Obs, use_obs
from arbius_tpu.obs.blocks import VOCABULARY, block_counts, block_map
from tests.test_pipeline import _SD15FakeRunner

SDS = jax.ShapeDtypeStruct


def _toy(p, x):
    with jax.named_scope("prefill"):
        with jax.named_scope("attention"):
            y = jnp.sin(x @ p)
        y = y * 2.0

    def body(c, _):
        return jnp.cos(c) @ jnp.eye(8) + c, None

    with jax.named_scope("decode"):
        y, _ = jax.lax.scan(body, y, jnp.arange(3))
    return y.T @ y


def _toy_map():
    compiled = jax.jit(_toy).lower(jnp.ones((8, 8)), jnp.ones((2, 8))) \
        .compile()
    return block_map(compiled.as_text())


def test_block_map_nests_scopes_and_leaves_compiler_copies_unblocked():
    bmap = _toy_map()
    paths = set(bmap.values())
    assert ("prefill", "attention") in paths      # nested, outermost first
    assert ("prefill",) in paths
    assert ("decode",) in paths                   # the loop's body and cond
    # the copy XLA makes of the loop's initial counter carries no op_name
    # and sits in the entry computation: no block
    copies = [n for n, p in bmap.items() if n.startswith("copy")]
    assert copies and all(bmap[n] == () for n in copies)
    assert set(block_counts(bmap)) == {"prefill", "attention", "decode",
                                       "unblocked"}


HLO = """\
HloModule m

%fused_a (param_0: f32[2]) -> f32[2] {
  %param_0 = f32[2]{0} parameter(0)
  %sine.1 = f32[2]{0} sine(%param_0), metadata={op_name="jit(f)/decode/while/body/attention/sin"}
  ROOT %cosine.1 = f32[2]{0} cosine(%sine.1), metadata={op_name="jit(f)/decode/while/body/attention/routed_experts/cos"}
}

%cmp (a: f32[], b: f32[]) -> pred[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %lt.9 = pred[] compare(%a, %b), direction=LT, metadata={op_name="prefill/lt"}
}

%body (t: (s32[], f32[2])) -> (s32[], f32[2]) {
  %t = (s32[], f32[2]{0}) parameter(0)
  %gte.1 = f32[2]{0} get-tuple-element(%t), index=1
  %fusion.3 = f32[2]{0} fusion(%gte.1), kind=kLoop, calls=%fused_a
  %copy.4 = f32[2]{0} copy(%fusion.3)
  %sort.5 = f32[2]{0} sort(%copy.4), dimensions={0}, to_apply=%cmp, metadata={op_name="jit(f)/decode/while/body/sort"}
  ROOT %tuple.1 = (s32[], f32[2]{0}) tuple(%gte.1, %sort.5)
}

%cond (t: (s32[], f32[2])) -> pred[] {
  %t.1 = (s32[], f32[2]{0}) parameter(0)
  ROOT %lt.2 = pred[] constant(false)
}

ENTRY %main (x: f32[2]) -> f32[2] {
  %x = f32[2]{0} parameter(0), metadata={op_name="x"}
  %copy.1 = f32[2]{0} copy(%x)
  %while.1 = (s32[], f32[2]{0}) while(%tuple.0), condition=%cond, body=%body, metadata={op_name="jit(f)/decode/while"}
  ROOT %fusion.9 = f32[2]{0} fusion(%x), kind=kLoop, calls=%fused_a, metadata={op_name="jit(f)/prefill/mul"}
}
"""


def test_block_map_rules_on_recorded_hlo():
    """Own op_name first; a fusion with none takes what its fused
    instructions share; else the loop that runs it; the entry's copy,
    with none of the three, is unblocked. Fusion bodies and comparators
    run as no operation of their own."""
    bmap = block_map(HLO)
    assert bmap["fusion.9"] == ("prefill",)          # its own op_name
    assert bmap["fusion.3"] == ("decode", "attention")
    assert bmap["copy.4"] == ("decode",)             # the loop's
    assert bmap["lt.2"] == ("decode",)
    assert bmap["sort.5"] == ("decode",)
    assert bmap["copy.1"] == ()
    assert "sine.1" not in bmap and "lt.9" not in bmap
    assert block_counts(bmap)["attention"] == 1


def _text_family(family):
    if family == "trinity":
        from arbius_tpu.models.trinity.model import TrinityConfig as C
        from arbius_tpu.models.trinity.pipeline import TrinityPipeline as P
    elif family == "deepseek_v32":
        from arbius_tpu.models.deepseek_v32.model import DeepSeekV32Config as C
        from arbius_tpu.models.deepseek_v32.pipeline import (
            DeepSeekV32Pipeline as P,
        )
    else:
        from arbius_tpu.models.joyai_flash.model import JoyAIFlashConfig as C
        from arbius_tpu.models.joyai_flash.pipeline import (
            JoyAIFlashPipeline as P,
        )
    return P(C.tiny(), prompt_buckets=(12,), decode_buckets=(4,), top_k=4)


def _bucket(family):
    """(bucket executable, abstract arguments) at the tiny size."""
    if family == "kandinsky2":
        from arbius_tpu.models.kandinsky2 import Kandinsky2Config
        from arbius_tpu.models.kandinsky2.pipeline import Kandinsky2Pipeline

        p = Kandinsky2Pipeline(Kandinsky2Config.tiny())
        shapes = jax.eval_shape(lambda: p.init_params(height=64, width=64))
        n = p.config.text.max_length
        return p.compiled_bucket(1, 64, 64, 2, "DDIM"), (
            shapes, SDS((1, n), jnp.int32), SDS((1,), jnp.float32),
            SDS((1,), jnp.uint32), SDS((1,), jnp.uint32))
    if family == "sd15":
        from arbius_tpu.models.sd15 import SD15Config
        from arbius_tpu.models.sd15.pipeline import SD15Pipeline

        p = SD15Pipeline(SD15Config.tiny())
        shapes = jax.eval_shape(p._init_fn(8, 8), jax.random.PRNGKey(0))
        n = p.config.text.max_length
        return p.compiled_bucket(1, 64, 64, 2, "DDIM"), (
            shapes, SDS((1, n), jnp.int32), SDS((1, n), jnp.int32),
            SDS((1,), jnp.float32), SDS((1,), jnp.uint32),
            SDS((1,), jnp.uint32))
    p = _text_family(family)
    shapes = jax.eval_shape(lambda: p.init_params(seed=0, dtype="bfloat16"))
    return p.compiled_bucket(2, 12, 4, "greedy"), (
        shapes, SDS((2, 12), jnp.int32), SDS((2,), jnp.uint32),
        SDS((2,), jnp.uint32))


@pytest.mark.parametrize("family", ["trinity", "deepseek_v32",
                                    "joyai_llm_flash", "kandinsky2", "sd15"])
def test_every_family_bucket_carries_its_whole_vocabulary(family):
    fn, args = _bucket(family)
    bmap = block_map(fn.lower(*args).compile().as_text())
    found = {b for path in bmap.values() for b in path}
    assert found == set(VOCABULARY[family])
    if family in ("deepseek_v32", "joyai_llm_flash", "trinity"):
        # a layer's blocks sit inside a phase (but where the compiler
        # fused work of both phases into one operation: it keeps what
        # the two share)
        blocked = [p for p in bmap.values() if p]
        phased = [p for p in blocked if p[0] in ("prefill", "decode")]
        assert len(phased) >= 0.97 * len(blocked)


def test_obs_builds_a_map_only_when_asked_and_compiles_nothing(monkeypatch):
    import arbius_tpu.obs.blocks as blocks_mod

    pipe = _text_family("trinity")
    params = pipe.init_params(seed=0, dtype="bfloat16")
    built = []
    real = blocks_mod.block_map
    monkeypatch.setattr(blocks_mod, "block_map",
                        lambda text, *a: built.append(1) or real(text, *a))
    obs = Obs()
    with use_obs(obs):
        for _ in range(2):
            pipe.generate(params, ["a cat", "a dog"], [1, 2],
                          prompt_bucket=12, decode_bucket=4)
    tag = pipe.bucket_tag(2, 12, 4, "greedy")
    assert set(obs.programs) == {tag}
    assert built == [] and obs._block_maps == {}
    events = []

    def listen(name, *_a, **_kw):
        events.append(name)

    jax.monitoring.register_event_duration_secs_listener(listen)
    jax.monitoring.register_event_listener(listen)
    try:
        bmap = obs.blocks(tag)
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
        jax.monitoring.unregister_event_listener(listen)
    assert not [e for e in events if "backend_compile" in e
                or "compilation_cache" in e]
    assert {b for p in bmap.values() for b in p} == set(VOCABULARY["trinity"])
    assert obs.blocks(tag) is bmap and len(built) == 1
    assert obs.blocks("no-such-tag") is None


class _TaggedRunner(_SD15FakeRunner):
    """A dispatch/finalize runner whose executable-cache tag is known
    (or whose derivation raises)."""

    def __init__(self, fails=False):
        super().__init__()
        self.fails = fails

    def cache_tag(self, hydrated, batch):
        if self.fails:
            raise KeyError("prompt")
        return f"fake.{batch}.{hydrated['prompt'][:2]}"


@pytest.mark.parametrize("fails", [False, True])
@pytest.mark.parametrize("staged", [True, False])
def test_solve_dispatch_names_its_program(staged, fails):
    """Each chunk's `solve.dispatch` names its executable's tag; a tag
    that cannot be derived leaves the attribute out and fails no solve."""
    from tests.test_node import drain, submit
    from tests.test_pipeline import PIPE_ON, _world

    eng, node, mid, _ = _world(_TaggedRunner(fails),
                               pipeline=PIPE_ON if staged else None,
                               canonical_batch=2)
    try:
        tids = [submit(eng, mid, prompt=f"t{i}") for i in range(3)]
        drain(node)
        assert all(bytes.fromhex(t[2:]) in eng.solutions for t in tids)
        progs = [e["attrs"].get("program") for e in node.obs.journal.events(
            kind="span") if e["name"] == "solve.dispatch"]
        assert progs == ([None, None] if fails
                         else ["fake.2.t0", "fake.2.t2"])
    finally:
        node.close()


def test_debug_blocks_view_counts_each_programs_blocks():
    from arbius_tpu.node.rpc import ControlRPC

    fn = jax.jit(_toy)
    args = (jnp.ones((8, 8)), jnp.ones((2, 8)))
    obs = Obs()
    obs.programs = {"toy.1": (fn, lambda: args)}

    class Node:
        pass

    rpc = ControlRPC.__new__(ControlRPC)
    rpc.node = Node()
    rpc.node.obs = obs
    code, doc = rpc.debug_view("/debug/blocks")
    assert code == 200
    counts = doc["programs"]["toy.1"]
    assert counts == block_counts(_toy_map())
    assert counts["attention"] >= 1 and counts["decode"] >= 1


def test_blocks_under_concurrent_requests_and_rebuilds():
    """/debug/blocks builds maps from request threads while the tick
    thread keeps new executables: every answer is a whole map of the
    executable kept under its tag, and nothing raises."""
    import sys
    import threading

    from arbius_tpu.obs import _keep_program

    class Exe:
        def __init__(self, text):
            self.text = text

        def as_text(self):
            return self.text

    exes = {t: Exe(HLO.replace("fusion.9", f"fusion.{t}"))
            for t in ("a", "b")}
    obs = Obs()
    for tag, exe in exes.items():
        _keep_program(obs, tag, exe, lambda: ())
    errors, done = [], threading.Event()

    def ask():
        try:
            while not done.is_set():
                for tag in ("a", "b"):
                    bmap = obs.blocks(tag)
                    assert bmap[f"fusion.{tag}"] == ("prefill",)
        except Exception as e:  # noqa: BLE001 — reported below
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=ask) for _ in range(8)]
        for t in threads:
            t.start()
        for _ in range(200):
            for tag, exe in exes.items():
                _keep_program(obs, tag, exe, lambda: ())
        done.set()
        for t in threads:
            t.join(timeout=30)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    assert errors == []
    assert set(obs.programs) == {"a", "b"}
