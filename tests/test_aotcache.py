"""AOT executable cache (docs/compile-cache.md) acceptance suite.

The non-negotiable is determinism: a disk-hit dispatch must produce
byte-identical results to a fresh-compile dispatch (pinned here for
the image probe mesh-off and dp2, the video-shaped seq probe, and a
real tiny SD-1.5 through solve_cid_batch), a corrupted or
wrong-environment entry must fall back to compile with a journaled
`aot_cache_reject` (never an error, never wrong bytes), and a drifted
program — the injected bf16-GroupNorm regression — must MISS, never
load stale. The fleet half: a 4-worker fleet over ONE shared cache
directory holds every SIM1xx invariant with zero rejects.
"""
from __future__ import annotations

import json
import math
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# fixed synthetic environment for entry-format tests: key derivation is
# pure over these, so goldens cannot depend on the host's jaxlib
FIXED_ENV = {"jax": "0.0-fixture", "jaxlib": "0.0-fixture",
             "platform": "cpu", "device_kind": "fixture-cpu",
             "device_count": 1}


def _write_fixture(cache_dir, program, arg_sig, payload, *, tag=None,
                   env=None, key=None):
    from arbius_tpu.aotcache import derive_key, make_header, write_entry

    env = env if env is not None else FIXED_ENV
    real_key = derive_key(program, env, arg_sig, "")
    key = key if key is not None else real_key
    return key, write_entry(
        cache_dir, key,
        make_header(key, program, env, arg_sig, payload, tag=tag),
        payload)


# -- entry format + key derivation ------------------------------------------

def test_entry_roundtrip_and_key_determinism(tmp_path):
    from arbius_tpu.aotcache import derive_key, read_entry, read_header

    payload = b"payload-bytes" * 100
    key, path = _write_fixture(str(tmp_path), "sha256:prog", "argsig",
                               payload, tag="sd15.1.64.64.2.DDIM")
    header, view, closer = read_entry(path)
    assert bytes(view) == payload
    closer()
    assert header["key"] == key
    assert header["tag"] == "sd15.1.64.64.2.DDIM"
    assert header["payload_len"] == len(payload)
    # pure + deterministic: same components → same key, any component
    # moves it — program (the graphlint fingerprint), environment
    # (jaxlib/platform/device), argument signature
    assert derive_key("sha256:prog", FIXED_ENV, "argsig") == key
    assert derive_key("sha256:DRIFT", FIXED_ENV, "argsig") != key
    assert derive_key("sha256:prog", dict(FIXED_ENV, jaxlib="9.9"),
                      "argsig") != key
    assert derive_key("sha256:prog", dict(FIXED_ENV, platform="tpu"),
                      "argsig") != key
    assert derive_key("sha256:prog", FIXED_ENV, "other") != key
    assert derive_key("sha256:prog", FIXED_ENV, "argsig", "donate") != key
    # header-only read is digest-checked too
    assert read_header(path)["key"] == key


def test_corrupt_truncated_and_doctored_entries_reject(tmp_path):
    from arbius_tpu.aotcache import CacheReject, read_entry, read_header

    payload = b"x" * 4096

    def reason_of(mutate, name, reader=read_header):
        d = tmp_path / name
        d.mkdir()
        _, path = _write_fixture(str(d), "sha256:p", "a", payload)
        mutate(path)
        with pytest.raises(CacheReject) as e:
            out = reader(path)
            if reader is read_entry:  # pragma: no cover — must raise
                out[2]()
        return e.value.reason

    def truncate(p):
        with open(p, "r+b") as f:
            f.truncate(os.path.getsize(p) - 100)

    def flip_payload(p):
        blob = bytearray(open(p, "rb").read())
        blob[-1] ^= 0xFF
        open(p, "wb").write(bytes(blob))

    def smash_magic(p):
        blob = bytearray(open(p, "rb").read())
        blob[0] = 0x00
        open(p, "wb").write(bytes(blob))

    from arbius_tpu.aotcache import read_entry

    assert reason_of(truncate, "t") == "truncated"
    # a bit-flip keeps the length: only the FULL (load-path / --verify)
    # read hashes the payload — the cheap header scan deliberately
    # doesn't (docs/compile-cache.md)
    assert reason_of(flip_payload, "f", reader=read_entry) == \
        "payload_digest_mismatch"
    assert reason_of(smash_magic, "m") == "bad_magic"


def test_concurrent_two_process_write_same_key(tmp_path):
    """tmp+rename under a real two-OS-process race: last-writer-wins,
    the surviving entry is whole (one writer's bytes, never torn), and
    both writers succeed."""
    from arbius_tpu.aotcache import derive_key, entry_path, read_entry

    key = derive_key("sha256:race", FIXED_ENV, "a")
    script = (
        "import sys\n"
        f"sys.path.insert(0, {REPO!r})\n"
        "from arbius_tpu.aotcache import make_header, write_entry\n"
        "key, d, marker = sys.argv[1], sys.argv[2], sys.argv[3]\n"
        f"env = {FIXED_ENV!r}\n"
        "payload = marker.encode() * 4096\n"
        "for _ in range(30):\n"
        "    write_entry(d, key, make_header(key, 'sha256:race', env,"
        " 'a', payload, tag=marker), payload)\n")
    procs = [subprocess.Popen(
        [sys.executable, "-c", script, key, str(tmp_path), marker])
        for marker in ("AAAA", "BBBB")]
    for p in procs:
        assert p.wait(timeout=120) == 0
    header, view, closer = read_entry(entry_path(str(tmp_path), key))
    blob = bytes(view)
    closer()
    assert blob in (b"AAAA" * 4096, b"BBBB" * 4096), "torn entry"
    assert header["tag"] in ("AAAA", "BBBB")
    assert header["key"] == key


# -- the jit_cache_get disk tier --------------------------------------------

def _dispatch_probe(probe_cls, aot_dir, **probe_kw):
    """One probe life: dispatch twice under a fresh Obs (+ optional AOT
    cache); returns (bytes, obs)."""
    import numpy as np

    from arbius_tpu.aotcache import AotCache
    from arbius_tpu.obs import Obs, use_obs

    obs = Obs(journal_capacity=256)
    if aot_dir is not None:
        obs.aot_cache = AotCache(aot_dir)
    probe = probe_cls(**probe_kw)
    items = [({"prompt": "aot x"}, 7), ({"prompt": "aot y"}, 8)]
    with use_obs(obs):
        out = np.asarray(probe.dispatch(items)).tobytes()
        np.asarray(probe.dispatch(items))  # memory-tier hit
    return out, obs


def _counters(obs):
    reg = obs.registry
    return {
        "mem_hits": reg.counter("arbius_jit_cache_hits_total",
                                labelnames=("tier",)).value(tier="memory"),
        "disk_hits": reg.counter("arbius_jit_cache_hits_total",
                                 labelnames=("tier",)).value(tier="disk"),
        "misses": reg.counter("arbius_jit_cache_misses_total").value(),
        "loads": reg.counter("arbius_aot_cache_loads_total").value(),
        "writes": reg.counter("arbius_aot_cache_writes_total").value(),
        "rejects": reg.counter("arbius_aot_cache_rejects_total").value(),
        "compiles": reg.histogram("arbius_compile_seconds").count(),
        "load_obs": reg.histogram("arbius_aot_load_seconds").count(),
    }


def test_image_probe_disk_tier_bytes_and_metrics(tmp_path):
    """The whole tier story on the image probe: cache-off == cold-write
    == warm-load bytes; hits split by tier; compile recorded on the
    miss life, load seconds on the hit life; warm set fed either way."""
    from arbius_tpu.parallel.meshsolve import ShardedImageProbe

    d = str(tmp_path / "cache")
    off, _ = _dispatch_probe(ShardedImageProbe, None)
    cold, obs_cold = _dispatch_probe(ShardedImageProbe, d)
    warm, obs_warm = _dispatch_probe(ShardedImageProbe, d)
    assert off == cold == warm
    c = _counters(obs_cold)
    assert c["misses"] == 1 and c["writes"] == 1 and c["compiles"] == 1
    assert c["disk_hits"] == 0 and c["mem_hits"] == 1
    w = _counters(obs_warm)
    assert w["disk_hits"] == 1 and w["loads"] == 1 and w["load_obs"] == 1
    assert w["misses"] == 0 and w["compiles"] == 0 and w["rejects"] == 0
    assert w["mem_hits"] == 1
    # the loaded executable is warm THIS life too (packer signal)
    assert "meshprobe.img.b2" in obs_warm.jit_warm
    h = obs_warm.registry.histogram("arbius_aot_load_seconds")
    assert h.recent()[0][0] == "meshprobe.img.b2"


def test_seq_probe_video_shaped_disk_tier_bytes(tmp_path):
    from arbius_tpu.parallel.meshsolve import ShardedSeqProbe

    d = str(tmp_path / "cache")
    off, _ = _dispatch_probe(ShardedSeqProbe, None, frames=4)
    cold, _ = _dispatch_probe(ShardedSeqProbe, d, frames=4)
    warm, obs_warm = _dispatch_probe(ShardedSeqProbe, d, frames=4)
    assert off == cold == warm
    w = _counters(obs_warm)
    assert w["disk_hits"] == 1 and w["rejects"] == 0


def test_dp2_mesh_disk_tier_bytes(tmp_path):
    """Meshed program through the disk tier on the 8-way CPU harness:
    dp2 bytes are identical across compile and deserialize lives (and,
    per the meshsolve pins, to mesh-off)."""
    from arbius_tpu.parallel import meshsolve
    from arbius_tpu.parallel.meshsolve import ShardedImageProbe

    mesh = meshsolve.boot_mesh({"dp": 2})
    d = str(tmp_path / "cache")
    off, _ = _dispatch_probe(ShardedImageProbe, None, mesh=mesh)
    cold, _ = _dispatch_probe(ShardedImageProbe, d, mesh=mesh)
    warm, obs_warm = _dispatch_probe(ShardedImageProbe, d, mesh=mesh)
    assert off == cold == warm
    w = _counters(obs_warm)
    assert w["disk_hits"] == 1 and w["rejects"] == 0


def test_corrupt_entry_falls_back_to_compile(tmp_path):
    """A truncated entry journals `aot_cache_reject`, the dispatch
    compiles fresh (same bytes), and a good entry is re-published."""
    from arbius_tpu.aotcache.store import scan
    from arbius_tpu.parallel.meshsolve import ShardedImageProbe

    d = str(tmp_path / "cache")
    cold, _ = _dispatch_probe(ShardedImageProbe, d)
    (key, path, size), = scan(d)
    with open(path, "r+b") as f:
        f.truncate(size // 2)
    again, obs = _dispatch_probe(ShardedImageProbe, d)
    assert again == cold
    c = _counters(obs)
    assert c["rejects"] == 1 and c["disk_hits"] == 0 and c["writes"] == 1
    (ev,) = obs.journal.events(kind="aot_cache_reject")
    assert ev["reason"] == "truncated" and ev["key"] == key
    # the rewrite healed the cache: next life disk-hits again
    healed, obs2 = _dispatch_probe(ShardedImageProbe, d)
    assert healed == cold and _counters(obs2)["disk_hits"] == 1


def test_wrong_environment_entry_rejects_not_loads(tmp_path):
    """An entry whose header claims another environment under the key
    this process would look up must reject (env_mismatch), never
    deserialize — and the boot warm scan must exclude it."""
    import jax.numpy as jnp

    import jax

    from arbius_tpu.aotcache import AotCache
    from arbius_tpu.obs import Obs, use_obs

    d = str(tmp_path / "cache")
    obs = Obs(journal_capacity=64)
    cache = AotCache(d)
    obs.aot_cache = cache
    jfn = jax.jit(lambda x: x + 1.0)
    args = (jnp.ones((4,)),)
    key = cache.key_for(jfn, args)
    # doctored file AT the real key, claiming a foreign environment
    _write_fixture(d, "sha256:foreign", "a", b"Z" * 256,
                   env=dict(FIXED_ENV, platform="tpu"), key=key,
                   tag="foreign.tag")
    assert cache.tags() == frozenset()  # warm scan: env-filtered
    with use_obs(obs):
        assert cache.load(key, tag="t") is None
    (ev,) = obs.journal.events(kind="aot_cache_reject")
    assert ev["reason"] == "env_mismatch"


def test_layout_mismatched_entries_are_not_disk_warm(tmp_path):
    """Differently-laid-out workers sharing one directory: a dp2
    worker's entries are real executables a single-device worker
    cannot load (different fingerprint ⇒ different key), so the warm
    scan must filter on the writer's layout stamp — otherwise the
    packer would warm-boost exactly the buckets it cannot load."""
    from arbius_tpu.aotcache import (
        AotCache,
        derive_key,
        env_signature,
        make_header,
        write_entry,
    )

    d = str(tmp_path / "shared")
    env = env_signature()
    for layout, tag in (("single", "sd15.single-tag"),
                        ("dp2", "sd15.dp2-tag")):
        key = derive_key("sha256:" + tag, env, "a")
        write_entry(d, key, make_header(key, "sha256:" + tag, env, "a",
                                        b"P" * 32, tag=tag,
                                        layout=layout), b"P" * 32)
    assert AotCache(d).tags() == frozenset({"sd15.single-tag"})
    assert AotCache(d, layout="dp2").tags() == \
        frozenset({"sd15.dp2-tag"})


def test_lru_eviction_under_max_bytes(tmp_path):
    """Budget fits one entry: publishing a second evicts the older
    (mtime) one, keeps the just-written one, counts + journals it."""
    import jax.numpy as jnp

    import jax

    from arbius_tpu.aotcache import AotCache
    from arbius_tpu.aotcache.store import scan, total_bytes
    from arbius_tpu.obs import Obs, use_obs

    d = str(tmp_path / "cache")
    obs = Obs(journal_capacity=64)
    cache = AotCache(d)
    obs.aot_cache = cache
    args = (jnp.ones((4,)),)
    with use_obs(obs):
        cache.get_or_compile(lambda: jax.jit(lambda x: x + 1.0),
                             lambda: args, tag="t1")
        (k1, p1, _), = scan(d)
        os.utime(p1, (1, 1))  # decisively the LRU entry
        cache.max_bytes = total_bytes(d) + 16
        cache.get_or_compile(lambda: jax.jit(lambda x: x * 3.0),
                             lambda: args, tag="t2")
    keys = [k for k, _, _ in scan(d)]
    assert k1 not in keys and len(keys) == 1
    reg = obs.registry
    assert reg.counter("arbius_aot_cache_evictions_total").value() == 1
    (ev,) = obs.journal.events(kind="aot_cache_evict")
    assert ev["keys"] == [k1]
    # tags() now only knows the survivor
    assert cache.tags() == frozenset({"t2"})


def test_key_derivation_failure_degrades_to_lazy_path(tmp_path):
    """The cache must never be WHY a solve fails: an args thunk that
    raises degrades to the exact pre-AOT contract (lazy jitted fn,
    warm=False so the dispatch times the first call), with a journaled
    `aot_cache_skip` — and nothing is written."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from arbius_tpu.aotcache import AotCache
    from arbius_tpu.aotcache.store import scan
    from arbius_tpu.obs import Obs, jit_cache_get, use_obs

    d = str(tmp_path / "cache")
    obs = Obs(journal_capacity=64)
    obs.aot_cache = AotCache(d)

    def boom():
        raise RuntimeError("no args for you")

    with use_obs(obs):
        fn, warm, tag = jit_cache_get(
            {}, 1, lambda: jax.jit(lambda x: x + 1.0), tag="t",
            aot_args=boom)
    assert not warm, "fallback must keep the lazy-path timing contract"
    assert np.asarray(fn(jnp.ones((2,)))).tolist() == [2.0, 2.0]
    (ev,) = obs.journal.events(kind="aot_cache_skip")
    assert ev["reason"].startswith("key_derivation: RuntimeError")
    assert obs.registry.counter(
        "arbius_aot_cache_skips_total").value() == 1
    assert scan(d) == []
    assert "t" in obs.jit_warm  # compiles at first dispatch, like pre-AOT


def test_store_write_failure_does_not_fail_the_solve(tmp_path):
    """An unwritable shared cache path (here: a plain file squatting on
    the directory name — chmod tricks don't bind under root): the
    compile succeeds, the publish skips with a journaled reason, the
    dispatch result stands."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from arbius_tpu.aotcache import AotCache
    from arbius_tpu.obs import Obs, jit_cache_get, use_obs

    d = tmp_path / "not-a-dir"
    d.write_bytes(b"squatter")
    obs = Obs(journal_capacity=64)
    obs.aot_cache = AotCache(str(d))
    with use_obs(obs):
        fn, warm, _ = jit_cache_get(
            {}, 1, lambda: jax.jit(lambda x: x * 2.0), tag="t",
            aot_args=lambda: (jnp.ones((2,)),))
    assert warm  # compiled eagerly — the write was what failed
    assert np.asarray(fn(jnp.ones((2,)))).tolist() == [2.0, 2.0]
    (ev,) = obs.journal.events(kind="aot_cache_skip")
    assert ev["reason"].startswith("write:")
    assert obs.registry.counter(
        "arbius_aot_cache_skips_total").value() == 1


# -- drift = miss, never stale (the invalidation-by-construction pin) -------

def _sd15_abstract_bucket(pipe):
    """(jitted bucket fn, abstract args) — key derivation needs only
    avals, so no params materialize and nothing compiles."""
    import jax
    import jax.numpy as jnp

    sds = jax.ShapeDtypeStruct
    shapes = jax.eval_shape(pipe._init_fn(8, 8), jax.random.PRNGKey(0))
    length = pipe.config.text.max_length
    args = (shapes, sds((1, length), jnp.int32), sds((1, length), jnp.int32),
            sds((1,), jnp.float32), sds((1,), jnp.uint32),
            sds((1,), jnp.uint32))
    return pipe._build_bucket(1, 64, 64, 2, "DDIM"), args


def test_drifted_bf16_groupnorm_program_misses_never_stale(
        tmp_path, monkeypatch):
    """The acceptance pin: the injected bf16-GroupNorm regression (the
    same perturbation test_graphlint drives through the golden gate)
    hashes to a DIFFERENT cache key with identical env/arg signatures —
    so a cache populated by the clean program answers the drifted one
    with a plain miss, never a stale load, never a reject."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    from arbius_tpu.aotcache import AotCache, args_signature
    from arbius_tpu.models.sd15 import SD15Config, SD15Pipeline
    from arbius_tpu.obs import Obs, use_obs

    cache = AotCache(str(tmp_path / "cache"))
    clean_pipe = SD15Pipeline(SD15Config.tiny())
    clean_fn, clean_args = _sd15_abstract_bucket(clean_pipe)
    clean_key = cache.key_for(clean_fn, clean_args)

    from arbius_tpu.models import common as common_mod
    from arbius_tpu.models.sd15 import unet as unet_mod
    from arbius_tpu.models.sd15 import vae as vae_mod

    class Bf16StatsGN(nn.Module):
        """GroupNorm statistics in ACTIVATION dtype — the regression
        graphlint's golden gate exists for (test_graphlint)."""
        num_groups: int = 32
        epsilon: float = 1e-5

        @nn.compact
        def __call__(self, x):
            g = math.gcd(x.shape[-1], self.num_groups)
            b, h, w, c = x.shape
            xg = x.reshape(b, h, w, g, c // g)
            n = h * w * (c // g)
            zero = jnp.zeros((), x.dtype)
            s = jax.lax.reduce(xg, zero, jax.lax.add, (1, 2, 4))
            mean = (s / n)[:, None, None, :, None]
            s2 = jax.lax.reduce(xg * xg, zero, jax.lax.add, (1, 2, 4))
            var = (s2 / n)[:, None, None, :, None] - mean * mean
            out = (xg - mean) * jax.lax.rsqrt(var + self.epsilon)
            return out.reshape(b, h, w, c)

    for mod in (common_mod, unet_mod, vae_mod):
        monkeypatch.setattr(mod, "GroupNorm32", Bf16StatsGN)
    drift_pipe = SD15Pipeline(SD15Config.tiny())
    drift_fn, drift_args = _sd15_abstract_bucket(drift_pipe)
    drift_key = cache.key_for(drift_fn, drift_args)

    assert drift_key != clean_key, \
        "a drifted program must hash to a different cache key"
    # the drifted CANONICAL FINGERPRINT alone moves the key: re-derive
    # both keys with the drifted program's own env/arg components and
    # only the program swapped — still different (the GN patch also
    # reshapes the param tree, so the live arg signature moves too;
    # this isolates the fingerprint's contribution)
    from arbius_tpu.aotcache import derive_key
    from arbius_tpu.analysis.graph.fingerprint import fingerprint

    import jax

    fp_clean = fingerprint(jax.make_jaxpr(clean_fn)(*clean_args))
    fp_drift = fingerprint(jax.make_jaxpr(drift_fn)(*drift_args))
    assert fp_clean != fp_drift
    asig = args_signature(drift_args)
    assert derive_key(fp_clean, cache.env(), asig) != \
        derive_key(fp_drift, cache.env(), asig)

    # populate the clean key; the drifted lookup is a PLAIN miss
    _write_fixture(cache.dir, "sha256:whatever", "a", b"W" * 128,
                   env=cache.env(), key=clean_key, tag="clean")
    obs = Obs(journal_capacity=64)
    with use_obs(obs):
        assert cache.load(drift_key, tag="drift") is None
    assert obs.journal.events(kind="aot_cache_reject") == []
    assert obs.registry.counter(
        "arbius_aot_cache_rejects_total").value() == 0


# -- real tiny SD-1.5: CID byte-equality across tiers -----------------------

def test_sd15_cids_identical_cache_off_cold_warm(tmp_path):
    """A real (tiny) SD-1.5 solve through solve_cid_batch: cache-off,
    cold cache (compile+publish), and a fresh warm life (deserialize)
    must emit byte-identical CIDs and files."""
    from arbius_tpu.aotcache import AotCache
    from arbius_tpu.models.sd15 import SD15Config, SD15Pipeline
    from arbius_tpu.node.factory import tiny_byte_tokenizer
    from arbius_tpu.node.solver import (
        ModelRegistry,
        RegisteredModel,
        SD15Runner,
        solve_cid_batch,
    )
    from arbius_tpu.obs import Obs, use_obs
    from arbius_tpu.templates.engine import load_template

    cfg = SD15Config.tiny()
    params = SD15Pipeline(
        cfg, tokenizer=tiny_byte_tokenizer(cfg.text)).init_params(
        seed=0, height=64, width=64)
    tmpl = load_template("anythingv3")
    items = [({"prompt": "aot cat", "negative_prompt": "", "width": 64,
               "height": 64, "num_inference_steps": 2,
               "scheduler": "DDIM", "seed": 7}, 7)]
    d = str(tmp_path / "cache")

    def life(aot: bool):
        pipe = SD15Pipeline(cfg, tokenizer=tiny_byte_tokenizer(cfg.text))
        model = RegisteredModel(id="0x" + "11" * 32, template=tmpl,
                                runner=SD15Runner(pipe, params))
        ModelRegistry().register(model)
        obs = Obs(journal_capacity=64)
        if aot:
            obs.aot_cache = AotCache(d)
        with use_obs(obs):
            out = solve_cid_batch(model, items, canonical_batch=1)
        return out, obs

    off, _ = life(False)
    cold, obs_cold = life(True)
    warm, obs_warm = life(True)
    assert off == cold == warm  # (cid, files) pairs, bytes and all
    assert _counters(obs_cold)["writes"] == 1
    w = _counters(obs_warm)
    assert w["disk_hits"] == 1 and w["compiles"] == 0 and \
        w["rejects"] == 0


# -- cross-life warm boost (scheduler) --------------------------------------

class _TagFakeRunner:
    """Instant fake image runner that exposes the disk-warm join
    surface (`cache_tag`) the real runners defer to their pipelines."""

    def __call__(self, hydrated: dict, seed: int) -> dict:
        import hashlib

        canon = json.dumps({k: v for k, v in hydrated.items()
                            if k != "seed"}, sort_keys=True).encode()
        blob = hashlib.sha256(canon + seed.to_bytes(8, "big")).digest()
        return {"out-1.png": b"\x89PNG" + blob}

    def cache_tag(self, hydrated: dict, batch: int) -> str:
        return f"faketag.b{batch}.w{hydrated.get('width', 512)}"


def _mini_world(tmp_path, *, aot_dir=None, sched_on=True):
    from arbius_tpu.chain import WAD, Engine, TokenLedger
    from arbius_tpu.node import (
        LocalChain,
        MinerNode,
        MiningConfig,
        ModelConfig,
        ModelRegistry,
        RegisteredModel,
    )
    from arbius_tpu.node.config import AotCacheConfig, SchedConfig
    from arbius_tpu.templates.engine import load_template

    tok = TokenLedger()
    eng = Engine(tok, start_time=10_000)
    tok.mint(Engine.ADDRESS, 600_000 * WAD)
    miner, user = "0x" + "aa" * 20, "0x" + "01" * 20
    for a in (miner, user):
        tok.mint(a, 10**6 * WAD)
        tok.approve(a, Engine.ADDRESS, 10**30)
    mid = "0x" + eng.register_model(user, user, 0, b"{}").hex()
    registry = ModelRegistry()
    registry.register(RegisteredModel(
        id=mid, template=load_template("anythingv3"),
        runner=_TagFakeRunner()))
    chain = LocalChain(eng, miner)
    chain.validator_deposit(100 * WAD)
    node = MinerNode(
        chain,
        MiningConfig(models=(ModelConfig(id=mid, template="anythingv3"),),
                     canonical_batch=1, compile_cache=False,
                     sched=SchedConfig(enabled=sched_on)
                     if sched_on else SchedConfig(),
                     aot_cache=AotCacheConfig(enabled=True, dir=aot_dir)
                     if aot_dir else AotCacheConfig()),
        registry)
    node.boot(skip_self_test=True)
    return eng, node, mid, user


def test_disk_warm_buckets_count_as_warm_at_boot(tmp_path):
    """costsched's cross-life warm boost (docs/compile-cache.md): a
    bucket whose tag the boot scan found serialized packs as warm
    BEFORE anything compiled this life, and /debug/costmodel surfaces
    the disk-warm set."""
    from arbius_tpu.aotcache import env_signature
    from arbius_tpu.node.rpc import ControlRPC

    d = str(tmp_path / "shared")
    # a prior life (any fleet member) published this bucket
    _write_fixture(d, "sha256:prior", "a", b"P" * 64,
                   env=env_signature(), tag="faketag.b1.w768")
    eng, node, mid, user = _mini_world(tmp_path, aot_dir=d)
    assert node._disk_warm_tags == frozenset({"faketag.b1.w768"})
    (ev,) = node.obs.journal.events(kind="aot_cache_warm")
    assert ev["tags"] == ["faketag.b1.w768"]

    while node.tick():
        pass
    eng.submit_task(user, 0, user, bytes.fromhex(mid[2:]), 0,
                    json.dumps({"negative_prompt": "",
                                "prompt": "warm at boot"},
                               sort_keys=True).encode())
    for _ in range(16):
        if not node.tick() and eng.solutions:
            break
    assert eng.solutions, "task must solve"
    (packed,) = node._sched._last
    assert packed.warm, \
        "disk-warm bucket must pack warm before any compile this life"

    rpc = ControlRPC(node, port=0)
    code, payload = rpc.debug_view("/debug/costmodel")
    assert code == 200
    assert payload["aot_disk_warm"] == ["faketag.b1.w768"]
    json.dumps(payload, sort_keys=True)
    node.close()


def test_no_cache_no_disk_warm_and_cold_bucket_not_warm(tmp_path):
    eng, node, mid, user = _mini_world(tmp_path, aot_dir=None)
    assert node._disk_warm_tags == frozenset()
    while node.tick():
        pass
    eng.submit_task(user, 0, user, bytes.fromhex(mid[2:]), 0,
                    json.dumps({"negative_prompt": "", "prompt": "cold"},
                               sort_keys=True).encode())
    for _ in range(16):
        if not node.tick() and eng.solutions:
            break
    (packed,) = node._sched._last
    assert not packed.warm
    node.close()


# -- the 4-worker fleet over one shared cache dir ---------------------------

def test_fleet_shared_cache_dir_holds_invariants_zero_rejects(tmp_path):
    """Acceptance: a 4-worker fleet racing one clean event stream over
    ONE shared cache directory — real jitted probe programs — holds
    every applicable SIM1xx invariant (101-112) with zero
    `aot_cache_reject` events; the cache actually carried executables
    across workers (one compile+publish, three deserializes)."""
    from arbius_tpu.aotcache.store import scan
    from arbius_tpu.sim.fleet import FleetSimHarness
    from arbius_tpu.sim.invariants import check_all, classify_tasks
    from arbius_tpu.sim.scenario import FleetSpec, Scenario

    scn = Scenario(
        name="fleet-aot",
        description="4 workers, one shared AOT cache dir, clean faults",
        tasks=8, burst=4, strict=True, fleet=FleetSpec(workers=4))
    workdir = tmp_path / "fleetaot"
    workdir.mkdir()
    aot_dir = str(tmp_path / "shared-aot")
    harness = FleetSimHarness(scn, 1, str(workdir), aot_dir=aot_dir)
    result = harness.run()
    findings = check_all(result)
    assert not findings, (
        "invariant violations over the shared cache:\n  "
        + "\n  ".join(f.text() for f in findings))
    assert result.quiescent
    assert set(classify_tasks(result).values()) == {"claimed"}
    rejects = [e for e in result.journal_events
               if e.get("kind") == "aot_cache_reject"]
    assert rejects == [], "clean fleet run must have zero cache rejects"
    # workers tick sequentially in-process, so the split is exact: the
    # first dispatcher compiled + published, every later worker's first
    # dispatch deserialized the shared entry
    per_worker = [_counters(w.obs) for w in harness.workers]
    assert sum(c["writes"] for c in per_worker) == 1
    assert sum(c["compiles"] for c in per_worker) == 1
    loaders = [c for c in per_worker if c["loads"]]
    assert len(loaders) == 3, \
        "three of four workers must have deserialized, not compiled"
    assert sum(c["rejects"] for c in per_worker) == 0
    assert len(scan(aot_dir)) == 1, "one bucket ⇒ one shared entry"


# -- config + CLI -----------------------------------------------------------

def test_aot_cache_config_loads_and_validates():
    from arbius_tpu.node.config import ConfigError, load_config

    cfg = load_config({"aot_cache": {"enabled": True, "dir": "/x/y",
                                     "max_bytes": 123}})
    assert cfg.aot_cache.enabled and cfg.aot_cache.dir == "/x/y"
    assert cfg.aot_cache.max_bytes == 123
    assert not load_config({}).aot_cache.enabled  # default: off
    with pytest.raises(ConfigError, match="aot_cache.dir"):
        load_config({"aot_cache": {"enabled": True, "dir": ""}})
    with pytest.raises(ConfigError, match="aot_cache.max_bytes"):
        load_config({"aot_cache": {"max_bytes": -1}})
    with pytest.raises(ConfigError, match="aot_cache"):
        load_config({"aot_cache": {"unknown_key": 1}})


def _build_cli_fixture(cache_dir: str) -> None:
    """The deterministic fixture cache the CLI goldens pin: one valid
    entry, one whose header does not re-derive its key (AOT501), one
    truncated (AOT502). Everything fixed — synthetic env, fixed
    payloads — so reports are byte-stable on any host."""
    _write_fixture(cache_dir, "sha256:good", "argsA", b"GOOD" * 64,
                   tag="sd15.1.64.64.2.DDIM")
    _write_fixture(cache_dir, "sha256:renamed", "argsB", b"BADK" * 64,
                   tag="renamed.tag",
                   key="ab" * 32)  # filename ≠ derived key
    _, path = _write_fixture(cache_dir, "sha256:trunc", "argsC",
                             b"TRNC" * 64, tag="trunc.tag")
    with open(path, "r+b") as f:
        f.truncate(70)


def _run_cli(args):
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "aotcache.py")]
        + args, capture_output=True, text=True, timeout=120)
    return r.returncode, r.stdout


GOLDEN_DIR = os.path.join(REPO, "tests", "fixtures", "aotcache")


@pytest.mark.parametrize("mode,golden,rc_want", [
    (["--verify", "--json"], "verify.golden.json", 1),
    (["--list", "--json"], "list.golden.json", 0),
])
def test_cli_reports_pinned_byte_deterministic(tmp_path, mode, golden,
                                               rc_want):
    """`tools/aotcache.py` on the fixture cache: exit codes per the
    shared lint contract and byte-identical reports (tier-1 golden)."""
    d = str(tmp_path / "fixture")
    _build_cli_fixture(d)
    rc, out = _run_cli(["--dir", d] + mode)
    assert rc == rc_want
    with open(os.path.join(GOLDEN_DIR, golden)) as f:
        assert out == f.read()


def test_cli_verify_clean_and_usage_errors(tmp_path):
    d = str(tmp_path / "ok")
    _write_fixture(d, "sha256:good", "a", b"OK" * 32, tag="t")
    rc, out = _run_cli(["--dir", d, "--verify"])
    assert rc == 0 and "verified clean" in out
    rc, _ = _run_cli(["--dir", d])                      # no mode
    assert rc == 2
    rc, _ = _run_cli(["--dir", d, "--list", "--stats"])  # two modes
    assert rc == 2
    rc, _ = _run_cli(["--dir", d, "--gc"])               # gc w/o budget
    assert rc == 2


def test_cli_gc_applies_lru(tmp_path):
    d = str(tmp_path / "gc")
    _, p1 = _write_fixture(d, "sha256:old", "a", b"O" * 512, tag="old")
    os.utime(p1, (1, 1))
    _write_fixture(d, "sha256:new", "a", b"N" * 512, tag="new")
    rc, out = _run_cli(["--dir", d, "--gc", "--max-bytes", "1000",
                        "--json"])
    assert rc == 0
    doc = json.loads(out)
    assert len(doc["evicted"]) == 1 and doc["remaining_entries"] == 1
    from arbius_tpu.aotcache import read_header
    from arbius_tpu.aotcache.store import scan

    (entry,) = scan(d)
    assert read_header(entry[1])["tag"] == "new"
