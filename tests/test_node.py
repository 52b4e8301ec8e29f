"""Node integration tests — the event→job→solve→commit→reveal→claim loop
against the in-process fake chain, closing the reference's biggest test gap
(SURVEY.md §4: "no miner-loop unit tests").

The model here is a fake deterministic runner (bytes derived from
input+seed) so the protocol mechanics are tested without JAX; the real
SD-1.5 runner goes through the same `solve_cid` path (covered in
test_node_sd15.py).
"""
from __future__ import annotations

import json

import pytest

from arbius_tpu.chain import Engine, TokenLedger, WAD
from arbius_tpu.l0.cid import cid_hex, cid_of_solution_files
from arbius_tpu.node import (
    AutomineConfig,
    BootError,
    LocalChain,
    MinerNode,
    MiningConfig,
    ModelConfig,
    ModelRegistry,
    RegisteredModel,
    load_config,
)
from arbius_tpu.templates.engine import load_template

MINER = "0x" + "aa" * 20
OTHER = "0x" + "bb" * 20
USER = "0x" + "01" * 20
MODEL_ADDR = "0x" + "33" * 20


def fake_runner(hydrated: dict, seed: int) -> dict:
    """Deterministic in (input, seed); output depends on both."""
    blob = json.dumps({k: v for k, v in sorted(hydrated.items())
                       if k != "seed"}).encode() + seed.to_bytes(8, "big")
    return {"out-1.png": b"\x89PNG" + blob}


def build_world(*, evilmode=False, automine=None, miner_stake=100 * WAD,
                model_fee=0, **cfg_overrides):
    tok = TokenLedger()
    eng = Engine(tok, start_time=10_000)
    tok.mint(Engine.ADDRESS, 600_000 * WAD)
    for a in (MINER, OTHER, USER):
        tok.mint(a, 1_000 * WAD)
        tok.approve(a, Engine.ADDRESS, 10**30)
    mid_bytes = eng.register_model(USER, MODEL_ADDR, model_fee,
                                   b'{"meta":{"title":"anything"}}')
    mid = "0x" + mid_bytes.hex()

    template = load_template("anythingv3")
    registry = ModelRegistry()
    registry.register(RegisteredModel(id=mid, template=template,
                                      runner=fake_runner))
    chain = LocalChain(eng, MINER)
    if miner_stake:
        chain.validator_deposit(miner_stake)
    cfg = MiningConfig(evilmode=evilmode,
                       models=(ModelConfig(id=mid, template="anythingv3"),),
                       automine=automine or AutomineConfig(),
                       **cfg_overrides)
    node = MinerNode(chain, cfg, registry)
    node.boot()
    drain(node)  # settle the boot-queued stake job (re-queues at +600s)
    return eng, tok, chain, node, mid


def task_input(prompt="a cat"):
    # negative_prompt is required=true in the template (no default fallback
    # for required fields — hydrateInput parity, models.ts:163-168)
    return {"prompt": prompt, "negative_prompt": ""}


def submit(eng, mid, prompt="a cat", fee=0, sender=USER):
    return "0x" + eng.submit_task(
        sender, 0, sender, bytes.fromhex(mid[2:]), fee,
        json.dumps(task_input(prompt)).encode()).hex()


def drain(node, n=10):
    total = 0
    for _ in range(n):
        done = node.tick()
        total += done
        if done == 0:
            break
    return total


def expected_cid(eng, taskid, mid):
    from arbius_tpu.l0.commitment import taskid2seed
    from arbius_tpu.templates.engine import hydrate_input, load_template

    raw = json.loads(eng.task_input_data[bytes.fromhex(taskid[2:])])
    hydrated = hydrate_input(raw, load_template("anythingv3"))
    hydrated["seed"] = taskid2seed(taskid)
    files = fake_runner(hydrated, hydrated["seed"])
    return cid_hex(cid_of_solution_files(files))


# -- happy path ------------------------------------------------------------

def test_task_to_solution_to_claim():
    eng, tok, chain, node, mid = build_world()
    tid = submit(eng, mid, fee=10 * WAD)
    drain(node)
    sol = eng.solutions[bytes.fromhex(tid[2:])]
    assert sol.validator == MINER
    assert "0x" + sol.cid.hex() == expected_cid(eng, tid, mid)
    assert node.metrics.solutions_submitted == 1
    # claim is time-gated
    bal0 = tok.balance_of(MINER)
    eng.advance_time(2000 + 121)
    drain(node)
    assert node.metrics.solutions_claimed == 1
    assert tok.balance_of(MINER) - bal0 == 9 * WAD  # 10 - 10% treasury cut


def test_solution_is_deterministic_per_taskid():
    eng, _, _, node, mid = build_world()
    t1 = submit(eng, mid, prompt="same prompt")
    t2 = submit(eng, mid, prompt="same prompt")
    drain(node)
    c1 = eng.solutions[bytes.fromhex(t1[2:])].cid
    c2 = eng.solutions[bytes.fromhex(t2[2:])].cid
    assert c1 != c2  # different taskid ⇒ different seed ⇒ different bytes


def test_unknown_model_ignored():
    eng, _, _, node, mid = build_world()
    other_model = eng.register_model(USER, MODEL_ADDR, 0, b"other template")
    eng.submit_task(USER, 0, USER, other_model, 0,
                    json.dumps(task_input()).encode())
    assert drain(node) == 0
    # only the re-queued stake heartbeat remains
    assert node.db.job_count() == 1


def test_min_fee_filter():
    eng, tok, chain, node, mid = build_world()
    m = node.registry.get(mid)
    node.registry.register(
        RegisteredModel(id=mid, template=m.template, runner=m.runner,
                        min_fee=5 * WAD))
    t_low = submit(eng, mid, fee=1 * WAD)
    t_ok = submit(eng, mid, fee=5 * WAD)
    drain(node)
    assert bytes.fromhex(t_low[2:]) not in eng.solutions
    assert bytes.fromhex(t_ok[2:]) in eng.solutions


def test_invalid_input_marks_task_and_contests_others_solution():
    """Garbage task input → mark invalid; when OTHER solves it anyway, the
    node contests (index.ts:236-266 flow)."""
    eng, tok, chain, node, mid = build_world()
    other_chain = LocalChain(eng, OTHER)
    other_chain.validator_deposit(100 * WAD)
    tid_b = eng.submit_task(USER, 0, USER, bytes.fromhex(mid[2:]), 0,
                            b"this is not json")
    tid = "0x" + tid_b.hex()
    drain(node)
    assert node.db.is_invalid_task(tid)
    assert tid_b not in eng.solutions
    # other miner reveals some CID for the invalid task
    bad_cid = "0x1220" + "cc" * 32
    other_chain.signal_commitment(
        other_chain.generate_commitment(tid, bad_cid))
    other_chain.submit_solution(tid, bad_cid)
    drain(node)
    assert node.metrics.contestations_submitted == 1
    con = eng.contestations[tid_b]
    assert con.validator == MINER


def test_evilmode_contested_by_honest_node():
    """Evil miner commits the sentinel-wrong CID; honest node computes the
    real one, sees the mismatch, contests, and wins the vote."""
    eng, tok, chain, evil_node, mid = build_world(evilmode=True)
    # honest node shares the same fake chain
    honest_chain = LocalChain(eng, OTHER)
    honest_chain.validator_deposit(100 * WAD)
    template = load_template("anythingv3")
    registry = ModelRegistry()
    registry.register(RegisteredModel(id=mid, template=template,
                                      runner=fake_runner))
    honest = MinerNode(honest_chain,
                       MiningConfig(models=(ModelConfig(id=mid,
                                                        template="anythingv3"),)),
                       registry)
    honest.boot()

    tid = submit(eng, mid)
    drain(evil_node)   # evil wins the race with a wrong CID
    sol = eng.solutions[bytes.fromhex(tid[2:])]
    assert sol.cid.endswith(b"\x06\x66")
    drain(honest)      # honest computes real CID, mismatches, contests
    assert honest.metrics.contestations_submitted == 1
    tid_b = bytes.fromhex(tid[2:])
    assert eng.contestations[tid_b].validator == OTHER


def test_stake_auto_topup():
    """With supply active, the stake job tops up to minimum*(1+20%)."""
    tok = TokenLedger()
    eng = Engine(tok, start_time=10_000)
    tok.mint(Engine.ADDRESS, 590_000 * WAD)   # supply 10k → minimum 8
    tok.mint(MINER, 1_000 * WAD)
    tok.approve(MINER, Engine.ADDRESS, 10**30)
    chain = LocalChain(eng, MINER)
    node = MinerNode(chain, MiningConfig(), ModelRegistry())
    node.boot()
    drain(node)
    minimum = eng.get_validator_minimum()
    staked = eng.validators[MINER].staked
    assert staked >= minimum
    assert staked == pytest.approx(minimum * 1.2, rel=0.01)
    # job re-queued itself for later
    assert node.db.job_count() == 1


def test_automine_submits_and_solves_own_tasks():
    eng, tok, chain, node, mid = build_world()
    # model id only exists after deployment, so configure automine now and
    # queue its first job (boot would have, had the config been enabled)
    node.config = MiningConfig(
        models=node.config.models,
        automine=AutomineConfig(enabled=True, model=mid, fee=0,
                                input=task_input("self work"), delay=60))
    node.db.queue_job("automine", {}, priority=10)
    drain(node)
    # one automined task got solved by ourselves
    assert node.metrics.solutions_submitted == 1
    assert node.db.job_count() >= 1  # automine re-queued at +60s
    eng.advance_time(61)
    drain(node)
    assert node.metrics.solutions_submitted == 2


def test_boot_self_test_golden():
    eng, tok, chain, node, mid = build_world()
    m = node.registry.get(mid)
    inp = task_input("arbius test cat")
    from arbius_tpu.templates.engine import hydrate_input
    hydrated = hydrate_input(dict(inp), m.template)
    good = cid_hex(cid_of_solution_files(fake_runner(hydrated, 1337)))
    node.registry.register(RegisteredModel(
        id=mid, template=m.template, runner=m.runner,
        golden=(inp, 1337, good)))
    node.boot()  # passes
    node.registry.register(RegisteredModel(
        id=mid, template=m.template, runner=m.runner,
        golden=(inp, 1337, "0x1220" + "00" * 32)))
    with pytest.raises(BootError, match="self-test"):
        node.boot()


def test_version_check_halts_boot():
    eng, tok, chain, node, mid = build_world()
    eng.set_version(99)
    with pytest.raises(BootError, match="version"):
        node.boot()


def test_failed_jobs_quarantined():
    eng, tok, chain, node, mid = build_world()

    def broken_runner(hydrated, seed):
        raise RuntimeError("model exploded")

    m = node.registry.get(mid)
    node.registry.register(RegisteredModel(id=mid, template=m.template,
                                           runner=broken_runner))
    submit(eng, mid)
    drain(node)
    failed = node.db.failed_jobs()
    assert any(m == "solve" for m, _ in failed)
    # nothing stuck in the live queue except the stake heartbeat
    assert all(j.method == "validatorStake"
               for j in node.db.get_jobs(now=10**12))


def test_config_load_validation():
    from arbius_tpu.node import ConfigError

    cfg = load_config(json.dumps({
        "db_path": ":memory:",
        "models": [{"id": "0x" + "ab" * 32, "template": "anythingv3"}],
        "automine": {"enabled": True, "delay": 30},
    }))
    assert cfg.models[0].template == "anythingv3"
    assert cfg.automine.delay == 30
    with pytest.raises(ConfigError, match="unknown config keys"):
        load_config('{"not_a_key": 1}')


def test_solve_jobs_batch_into_one_dispatch():
    """Tasks sharing a shape bucket run as ONE runner batch (the dp win
    over the reference's strictly-serial solve queue, index.ts:555-563)."""
    eng, tok, chain, node, mid = build_world()
    batches = []

    class BatchRunner:
        def __call__(self, hydrated, seed):
            return self.run_batch([(hydrated, seed)])[0]

        def run_batch(self, items):
            batches.append(len(items))
            return [fake_runner(h, s) for h, s in items]

    m = node.registry.get(mid)
    node.registry.register(RegisteredModel(id=mid, template=m.template,
                                           runner=BatchRunner()))
    node.config = MiningConfig(models=node.config.models, canonical_batch=4)
    tids = [submit(eng, mid, prompt=f"p{i}") for i in range(3)]
    drain(node)
    # one dispatch, padded to the canonical batch (3 real + 1 pad)
    assert batches == [4]
    for tid in tids:
        assert bytes.fromhex(tid[2:]) in eng.solutions


def test_claim_latency_metrics_recorded():
    eng, tok, chain, node, mid = build_world()
    submit(eng, mid)
    drain(node)
    assert len(node.metrics.solve_latency) == 1
    assert len(node.metrics.stage_seconds["infer"]) == 1
    assert len(node.metrics.stage_seconds["commit"]) == 1


def test_db_prune_keeps_unclaimed():
    eng, tok, chain, node, mid = build_world()
    t_old = submit(eng, mid, prompt="old")
    drain(node)
    eng.advance_time(2200)
    drain(node)  # claimed
    t_new = submit(eng, mid, prompt="new")
    drain(node)  # solved but NOT claimed yet
    removed = node.db.prune_before(eng.now + 10**6)
    assert removed == 1
    assert node.db.get_task(t_old) is None
    assert node.db.get_task(t_new) is not None


def test_delegated_validator_stake_seam():
    """blockchain.ts:44-67 seam: with `delegated_validator` configured,
    stake reads AND the auto-top-up deposit target the delegated address
    (validatorDeposit is anyone-may-top-up, EngineV1.sol:581-604); the
    node's own wallet pays but never accrues stake."""
    delegated = "0x" + "dd" * 20
    tok = TokenLedger()
    eng = Engine(tok, start_time=10_000)
    tok.mint(Engine.ADDRESS, 590_000 * WAD)   # supply 10k → minimum 8
    tok.mint(MINER, 1_000 * WAD)
    tok.approve(MINER, Engine.ADDRESS, 10**30)
    chain = LocalChain(eng, MINER, validator_address=delegated)
    node = MinerNode(chain, MiningConfig(delegated_validator=delegated),
                     ModelRegistry())
    import logging
    records = []
    h = logging.Handler()
    h.emit = records.append
    logging.getLogger("arbius.node").addHandler(h)
    try:
        node.boot()
    finally:
        logging.getLogger("arbius.node").removeHandler(h)
    # the solving-gate caveat must be surfaced at boot, not at first revert
    assert any("delegated_validator" in r.getMessage() for r in records)
    drain(node)
    minimum = eng.get_validator_minimum()
    assert eng.validators[delegated].staked >= minimum
    assert MINER not in eng.validators
    # facade reads report the delegated stake
    assert chain.validator_staked() == eng.validators[delegated].staked

    from arbius_tpu.node.config import ConfigError
    with pytest.raises(ConfigError, match="delegated_validator"):
        MiningConfig(delegated_validator="not-an-address")


# -- lost-response recovery (found by simnet rpc-flap) ---------------------

def _lost_response(fn):
    """Wrap a chain tx method so it LANDS but the response is lost —
    the classic flaky-endpoint failure the retry envelope then sees as
    'already done' reverts."""
    def wrapped(*args, **kwargs):
        fn(*args, **kwargs)
        raise OSError("sim: response lost after landing")
    return wrapped


def test_reveal_lost_response_still_schedules_claim():
    eng, tok, chain, node, mid = build_world()
    chain.submit_solution = _lost_response(chain.submit_solution)
    tid = submit(eng, mid, fee=10 * WAD)
    drain(node)
    sol = eng.solutions[bytes.fromhex(tid[2:])]
    assert sol.validator == MINER
    # the reveal landed even though every attempt "failed": the node must
    # recognize its own on-chain solution and keep the lifecycle going
    assert node.metrics.solutions_submitted == 1
    assert node.db.has_job("claim", {"taskid": tid})
    eng.advance_time(2000 + 121)
    drain(node)
    assert node.metrics.solutions_claimed == 1


def test_claim_lost_response_still_counts():
    eng, tok, chain, node, mid = build_world()
    tid = submit(eng, mid, fee=10 * WAD)
    drain(node)
    chain.claim_solution = _lost_response(chain.claim_solution)
    eng.advance_time(2000 + 121)
    drain(node)
    assert eng.solutions[bytes.fromhex(tid[2:])].claimed
    assert node.metrics.solutions_claimed == 1
    # nothing quarantined: the exhausted retries resolved to success
    assert node.db.failed_jobs() == []


def test_reveal_never_landing_quarantines_visibly():
    eng, tok, chain, node, mid = build_world()

    def down(*a, **k):
        raise OSError("sim: endpoint down")

    chain.submit_solution = down
    tid = submit(eng, mid)
    drain(node)
    # no silent drop: the solve job must land in failed_jobs (task
    # conservation — simnet SIM101)
    assert ("solve" in {m for m, d in node.db.failed_jobs()
                        if d.get("taskid") == tid})
    assert bytes.fromhex(tid[2:]) not in eng.solutions


def test_stake_heartbeat_survives_chain_fault():
    eng, tok, chain, node, mid = build_world()
    orig = chain.validator_staked

    def down():
        raise OSError("sim: endpoint down")

    chain.validator_staked = down
    eng.advance_time(700)
    drain(node)
    # the job failed and was quarantined...
    assert any(m == "validatorStake" for m, _ in node.db.failed_jobs())
    # ...but the heartbeat re-queued itself (a dead stake loop would
    # eventually deregister the validator — found by simnet rpc-flap)
    assert node.db.has_job("validatorStake", {})
    chain.validator_staked = orig
    eng.advance_time(700)
    drain(node)


def test_get_jobs_orders_priority_desc_then_id_asc():
    """The fleet reclaim path leans on this ordering (docs/fleet.md):
    priority DESC, insertion id ASC on ties — a re-queued job never
    jumps ahead of an older sibling at the same priority."""
    from arbius_tpu.node import NodeDB

    db = NodeDB(":memory:")
    ids = [db.queue_job("a", {"n": i}) for i in range(3)]          # prio 0
    hi = db.queue_job("hot", {}, priority=50)
    mid = db.queue_job("warm", {}, priority=10)
    jobs = db.get_jobs(now=0)
    assert [j.id for j in jobs] == [hi, mid] + ids
    # ties keep insertion order even after interleaved deletes
    db.delete_job(ids[1])
    assert [j.data.get("n") for j in db.get_jobs(now=0)
            if j.method == "a"] == [0, 2]
    db.close()


def test_get_jobs_limit_boundary_exactly_hit():
    from arbius_tpu.node import NodeDB

    db = NodeDB(":memory:")
    for i in range(101):
        db.queue_job("a", {"n": i})
    assert len(db.get_jobs(now=0)) == 100          # default limit
    assert len(db.get_jobs(now=0, limit=101)) == 101
    assert len(db.get_jobs(now=0, limit=1)) == 1
    db.close()


def test_get_jobs_excludes_future_waituntil():
    from arbius_tpu.node import NodeDB

    db = NodeDB(":memory:")
    due = db.queue_job("now", {}, waituntil=100)
    edge = db.queue_job("edge", {}, waituntil=200)
    db.queue_job("later", {}, waituntil=201)
    assert [j.id for j in db.get_jobs(now=100)] == [due]
    # waituntil == now is DUE (<=), one second later is not
    assert [j.id for j in db.get_jobs(now=200)] == [due, edge]
    db.close()


# -- solve intake: top-up to whole canonical batches ------------------------
# docs/scheduler.md "Solve intake": a tick's 100-job window holds 50
# solves when every task also queued a pinTaskInput; a bucket left short
# of a whole canonical batch takes further due solves of its key from
# past the window, at most canonical_batch - 1 a key a tick.

class _SlotRunner:
    """Batched fake runner: records each dispatch's (slots, distinct
    seeds) — a padded slot repeats the chunk's last real item."""

    def __init__(self):
        self.chunks = []

    def __call__(self, hydrated, seed):
        return self.run_batch([(hydrated, seed)])[0]

    def run_batch(self, items):
        self.chunks.append((len(items), len({s for _, s in items})))
        return [fake_runner(h, s) for h, s in items]


def _intake_world(tmp_path, cb, runner=None, **cfg):
    """A node that pins (so each task queues pinTaskInput + solve) at
    canonical batch `cb`; returns (eng, node, mid, runner)."""
    eng, _, _, node, mid = build_world(store_dir=str(tmp_path / "store"),
                                       canonical_batch=cb, **cfg)
    runner = runner or _SlotRunner()
    m = node.registry.get(mid)
    node.registry.register(RegisteredModel(id=mid, template=m.template,
                                           runner=runner))
    return eng, node, mid, runner


def _submit_shaped(eng, mid, i, width=768):
    return "0x" + eng.submit_task(
        USER, 0, USER, bytes.fromhex(mid[2:]), 0,
        json.dumps({**task_input(f"p{i}"), "width": width}).encode()).hex()


def _topped(node):
    return node.obs.registry.counter(
        "arbius_solve_intake_topped_total", labelnames=("model",))


def _solved(eng, tids):
    return {t for t in tids if bytes.fromhex(t[2:]) in eng.solutions}


def _tops(node):
    """The `topped` attribute of each solve.batch / solve.pipeline."""
    return [e["attrs"]["topped"] for e in node.obs.journal.events(kind="span")
            if e["name"] in ("solve.batch", "solve.pipeline")]


@pytest.mark.parametrize("pipelined", [False, True])
def test_intake_fills_buckets_before_padding(tmp_path, pipelined):
    """60 due solves of one shape at canonical batch 8: the second tick
    takes the window's 50 plus 6 from past it (7 whole chunks), so the
    drain pads one chunk, not two (50 -> 6x8+2, 10 -> 8+2)."""
    from arbius_tpu.node.config import PipelineConfig

    eng, node, mid, runner = _intake_world(
        tmp_path, 8, pipeline=PipelineConfig(enabled=pipelined))
    tids = [submit(eng, mid, prompt=f"p{i}") for i in range(60)]
    node.tick()                      # task jobs: 60 pins + 60 solves queued
    node.tick()
    assert len(_solved(eng, tids)) == 56
    assert _topped(node).value(model=mid) == 6
    drain(node)
    assert _solved(eng, tids) == set(tids)
    padded = [c for c in runner.chunks if c[0] != c[1]]
    assert len(padded) == 1 and runner.chunks.count((8, 8)) == 7
    assert _tops(node) == [6, 0]


def test_intake_top_up_keeps_lone_solve_cids(tmp_path):
    """A task's CID does not depend on the chunk it rides in: every CID
    of a topped-up drain equals the task's CID solved alone (a jitted
    per-sample-seeded probe, so batch and lone are different programs)."""
    from arbius_tpu.l0.commitment import taskid2seed
    from arbius_tpu.parallel.meshsolve import ShardedImageProbe
    from arbius_tpu.templates.engine import hydrate_input

    probe = ShardedImageProbe()
    eng, node, mid, _ = _intake_world(tmp_path, 8, runner=probe,
                                      compile_cache=False)
    tids = [submit(eng, mid, prompt=f"p{i}") for i in range(60)]
    drain(node)
    assert _topped(node).value(model=mid) == 6
    template = load_template("anythingv3")
    for tid in tids:
        raw = json.loads(eng.task_input_data[bytes.fromhex(tid[2:])])
        hydrated = hydrate_input(raw, template)
        hydrated["seed"] = taskid2seed(tid)
        alone = cid_hex(cid_of_solution_files(probe(hydrated,
                                                    hydrated["seed"])))
        assert "0x" + eng.solutions[bytes.fromhex(tid[2:])].cid.hex() \
            == alone


def test_intake_top_up_skips_solves_not_yet_due(tmp_path):
    """Solves past the window whose waituntil lies ahead stay queued:
    the short bucket pads rather than take a job before it is due."""
    eng, node, mid, runner = _intake_world(tmp_path, 8)
    tids = [submit(eng, mid, prompt=f"p{i}") for i in range(60)]
    node.tick()
    window = {j.id for j in node.db.get_jobs(node.chain.now)}
    later = [j for j in node.db.get_jobs(node.chain.now, limit=1000)
             if j.method == "solve" and j.id not in window]
    assert len(later) == 10
    for j in later:
        node.db.delete_job(j.id)
        node.db.queue_job("solve", j.data, waituntil=node.chain.now + 1)
    node.tick()
    assert len(_solved(eng, tids)) == 50
    assert _topped(node).value(model=mid) == 0
    assert runner.chunks[-1] == (8, 2)


@pytest.mark.parametrize("cb", [7, 8, 16])
def test_intake_top_up_takes_at_most_a_batch_less_one(tmp_path, cb):
    """Per key a tick the top-up takes -50 % cb solves (the window holds
    50), never more than cb - 1, though 40 more wait past the window."""
    eng, node, mid, _ = _intake_world(tmp_path, cb)
    tids = [submit(eng, mid, prompt=f"p{i}") for i in range(90)]
    node.tick()
    node.tick()
    took = -50 % cb                  # 7 -> 6: the cap itself
    assert took <= cb - 1
    assert _topped(node).value(model=mid) == took
    assert len(_solved(eng, tids)) == 50 + took
    assert _tops(node) == [took]


def test_intake_top_up_takes_only_its_own_bucket_key(tmp_path):
    """Past the window the queue holds solves of another shape between
    those of the short bucket's: only the short bucket's are taken."""
    eng, node, mid, runner = _intake_world(tmp_path, 8)
    own = [_submit_shaped(eng, mid, i) for i in range(50)]
    other = [_submit_shaped(eng, mid, 50 + i, width=512) for i in range(3)]
    own += [_submit_shaped(eng, mid, 53 + i) for i in range(4)]
    other += [_submit_shaped(eng, mid, 57 + i, width=512) for i in range(3)]
    node.tick()
    node.tick()
    assert _solved(eng, own + other) == set(own)
    assert _topped(node).value(model=mid) == 4
    assert runner.chunks[-1] == (8, 6)      # 54 = 6x8 + 6: nothing else


def test_intake_top_up_leaves_priority_jobs_in_their_tick(tmp_path,
                                                          monkeypatch):
    """A priority-50 contest job due in a topping tick still runs in it
    (it heads the window, which then holds 49 solves: 7 are taken)."""
    eng, node, mid, _ = _intake_world(tmp_path, 8)
    tids = [submit(eng, mid, prompt=f"p{i}") for i in range(60)]
    node.tick()
    ran = []
    monkeypatch.setattr(node, "_process_contest",
                        lambda data: ran.append(data["taskid"]))
    node.db.queue_job("contest", {"taskid": tids[0]}, priority=50)
    node.tick()
    assert ran == [tids[0]]
    assert _topped(node).value(model=mid) == 7
    assert len(_solved(eng, tids)) == 56


def test_intake_at_canonical_batch_one_is_the_window(tmp_path):
    """At canonical batch 1 nothing is short: each tick solves exactly
    the solves of its 100-job window, as before the top-up existed."""
    eng, node, mid, _ = _intake_world(tmp_path, 1)
    tids = [submit(eng, mid, prompt=f"p{i}") for i in range(60)]
    node.tick()
    window = [j.data["taskid"] for j in node.db.get_jobs(node.chain.now)
              if j.method == "solve"]
    assert len(window) == 50
    node.tick()
    assert _solved(eng, tids) == set(window)
    drain(node)
    assert _solved(eng, tids) == set(tids)
    assert _topped(node).value(model=mid) == 0
    assert _tops(node) == [0, 0]


def test_due_solves_past_pages_in_queue_order():
    """The top-up's reader: due solves only, held ids excluded, in
    priority DESC, id ASC order across its 100-row pages."""
    from arbius_tpu.node import NodeDB

    db = NodeDB(":memory:")
    ids = [db.queue_job("solve", {"n": i}) for i in range(230)]
    db.queue_job("pinTaskInput", {})
    db.queue_job("solve", {"n": "late"}, waituntil=10)
    hot = db.queue_job("solve", {"n": "hot"}, priority=5)
    held = ids[:40]
    got = [j.id for j in db.due_solves_past(0, held)]
    assert got == [hot] + ids[40:]
    late = [j.data["n"] for j in db.due_solves_past(10, ids)]
    assert late == ["hot", "late"]
    db.close()
