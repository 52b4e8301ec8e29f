"""One run of one cell: set-up, warm-up, the measured window, the numbers,
the comparison with the reference, one result line.

Nothing here names a cell, a configuration, a model or a metric: those
come from the manifest and the files it points to (perfbench/manifest.py).
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

EXIT_NO_CHIP = 4
EXIT_NO_PROGRAM = 5


class Run:
    """What a metric reader may look at."""

    def __init__(self):
        self.cell = None
        self.system = None
        self.window: dict = {}
        self.spans: list[dict] = []      # clipped to the traced window
        self.trace: dict | None = None   # reduced profiler trace, or None
        self.solutions = 0               # real solutions in that window
        self.tasks: list[dict] = []      # ... and which
        self.seconds = 0.0               # that window's length
        self.timings: dict = {}
        self.peaks: dict | None = None
        self.setup_s = 0.0
        self.flops_per_solution: dict = {}
        self.parts: dict = {}            # flops.count_parts per model
        self.first_task: dict = {}       # ... and the task counted, hydrated


def _note(t0):
    def note(msg: str) -> None:
        print(f"[perfbench +{time.perf_counter() - t0:.0f}s] {msg}",
              file=sys.stderr, flush=True)
    return note


def parse(argv):
    ap = argparse.ArgumentParser(description="run one benchmark cell once")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--manifest", default=None,
                    help="another manifest than BENCHMARK.json (the tests' "
                         "tiny rehearsal)")
    ap.add_argument("--control", default=None, choices=("fp8",),
                    help="the control that has to come out as not correct: "
                         "in the served answer's place, the reference "
                         "computed in fp8")
    return ap.parse_args(argv)


def _reduce_trace(run: Run, trace_dir: str, log, t0: float, t1: float):
    """Device busy/idle and the breakdown for the host window [t0, t1]."""
    from perfbench import trace_reduce as tr

    data = tr.load_xplane(tr.find_xplane(trace_dir))
    if not data["devices"]:
        raise RuntimeError("the profiler's trace has no device plane")
    per_dev = {plane: tr.op_events(lines)
               for plane, lines in data["devices"].items()}
    # The device's line and the host's spans have to share a clock. Two
    # ways to pin one to the other, and the one under which more of the
    # device's work falls inside the window is taken: (a) the first
    # bench.dispatch annotation, which is in the trace's host plane and in
    # the span log; (b) the end of the last whole bucket program on the
    # device ("XLA Modules" line), which is the moment block_until_ready
    # returned on the host (the last bench.device_wait's end).
    marks = sorted(s for n, s, _ in data["host"] if n == "bench.dispatch")
    mine = sorted(s["t0"] for s in log.spans if s["name"] == "bench.dispatch"
                  and t0 <= s["t0"] <= t1)
    waits = [s["t1"] for s in log.spans if s["name"] == "bench.device_wait"
             and t0 <= s["t1"] <= t1 + 1e-3]
    plane0 = sorted(per_dev)[0]
    modules = [e for n, evs in data["devices"][plane0].items()
               if "XLA Modules" in n for e in evs]
    shifts = {}
    if marks and mine:
        shifts["annotation"] = marks[0] - mine[0]
    if modules and waits:
        longest = max(d for _, _, d in modules)
        ends = [s + d for _, s, d in modules if d >= 0.5 * longest]
        shifts["module_end"] = max(ends) - max(waits)
    if not shifts:
        raise RuntimeError("the trace cannot be put on the host's clock: "
                           "no bench.dispatch annotation and no module line")

    def inside(shift):
        return tr.busy_seconds(tr.clip(per_dev[plane0], t0 + shift,
                                       t1 + shift))

    how = max(shifts, key=lambda k: inside(shifts[k]))
    shift = shifts[how]
    p0, p1 = t0 + shift, t1 + shift
    busy, events0 = [], None
    for plane in sorted(per_dev):
        evs = tr.clip(per_dev[plane], p0, p1)
        busy.append(tr.busy_seconds(evs))
        if events0 is None:
            events0 = evs
    gaps = tr.idle_gaps(events0, p0, p1)
    return {
        "busy_s": sum(busy) / len(busy), "window_s": p1 - p0,
        "events": events0, "gaps": gaps, "shift": shift,
        "aligned_by": how,
        "lines": {p: {n: len(e) for n, e in ls.items()}
                  for p, ls in data["devices"].items()},
        "breakdown": {
            "device_ops": tr.top_ops(events0),
            "idle_gaps": tr.name_gaps(gaps, run.spans,
                                      lambda p: p - shift),
        },
    }


def run_cell(args, t_start: float) -> tuple[int, dict | None]:
    note = _note(t_start)
    from perfbench import manifest

    cell = manifest.Cell(args.manifest or manifest.DEFAULT_MANIFEST,
                         args.workload)
    try:
        import arbius_tpu  # noqa: F401 — the system under test
    except ImportError as e:
        note(f"the program is not in this directory: {e}")
        return EXIT_NO_PROGRAM, None
    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    rehearsal = bool(cell.config.get("rehearsal"))
    if device["platform"] != "tpu" and not rehearsal:
        note(f"JAX found no accelerator ({device}); only a configuration "
             "marked as a rehearsal may run off the chip")
        return EXIT_NO_CHIP, None
    if len(devs) < cell.chips:
        note(f"the cell asks for {cell.chips} chip(s), JAX found {len(devs)}")
        return EXIT_NO_CHIP, None
    on_chip = device["platform"] == "tpu"

    from perfbench import correct, flops, peaks, spans, system, traffic

    run = Run()
    run.cell = cell
    run.peaks = peaks.peaks_for(device["kind"]) if on_chip else None
    sysm = system.System(cell.config, args.seed, note=note,
                         config_dir=cell.config_dir, family=cell.family)
    run.system = sysm
    trace_dir = None
    try:
        sysm.build()
        gen = traffic.Traffic(cell.traffic, args.seed)
        log = spans.SpanLog()
        sysm.warm_up(gen)
        if args.trace:
            # after the warm-up: a bucket traced from inside a wrapper
            # carries other source locations, so it would be another entry
            # of the compile cache than the --trace 0 run's
            for m in sysm.models:
                log.wrap_runner(
                    sysm.registry.get("0x" + m.id_bytes.hex()).runner,
                    m.template)
        note(f"persistent compile cache at {sysm.cache_dir}: "
             f"{sysm.cache_events['hits']} hits, "
             f"{sysm.cache_events['misses']} misses in set-up")
        run.timings = dict(sysm.timings)
        run.setup_s = time.perf_counter() - t_start
        warm_cache = dict(sysm.cache_events)

        # ---- the measured window ---------------------------------------
        stop_trace = None
        traced_end = {}
        if args.trace:
            trace_dir = tempfile.mkdtemp(prefix="perfbench-trace-")
            jax.profiler.start_trace(trace_dir)

            def stop_trace():
                traced_end["t"] = time.perf_counter()
                jax.profiler.stop_trace()
        win = sysm.window(gen, args.seconds, on_first_tick=stop_trace)
        run.window = win
        compiled_in_window = sysm.cache_events["misses"] \
            - warm_cache["misses"] + sysm.cache_events["hits"] \
            - warm_cache["hits"]
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devs[:cell.chips])
        device["memory_peak_bytes"] = int(peak)
        solved = [t for t in win["tasks"] if t["solved"] is not None]
        window_s = win["t1"] - win["t0"]
        note(f"window {window_s:.1f}s, {win['ticks']} tick(s), "
             f"{len(solved)}/{len(win['tasks'])} solved, peak "
             f"{peak / 2**30:.2f} GiB, {compiled_in_window} compile-cache "
             "lookups inside the window")

        # what the traced run's readers see: the first tick only
        t_end = traced_end.get("t", win["t1"])
        log.add_journal(sysm.node.obs.journal.events())
        run.spans = log.within(win["t0"], t_end)
        run.tasks = [t for t in solved if t["solved"] <= t_end + 1e-6]
        run.solutions = len(run.tasks)
        run.seconds = t_end - win["t0"]

        failed_jobs = sysm.failed_jobs()
        bad, served = correct.chain_checks(sysm, solved, system.MINER)
        bad += len(win["unsolved"]) + len(failed_jobs)
        per_model = dict(cell.traffic["check"]["buckets"])
        chosen = correct.sample(win["tasks"], per_model,
                                sysm.canonical_batch, args.seed)

        if args.trace:
            # model FLOPs per solution and per bucket, from shapes (nothing
            # is computed); only the traced run's readers want them
            for m in sysm.models:
                # at the shapes of the window's first task of the model
                # (a mix keeps each model's tasks to one shape)
                first = next((t["input"] for t in win["tasks"]
                              if t["model"] == m.template), None)
                if first is None:
                    continue
                task = run.first_task[m.template] = m.hydrated(first)
                run.parts[m.template] = {
                    b: flops.count_parts(m.family.reference, m.arch, task,
                                         m.params, batch=b)
                    for b in (1, sysm.canonical_batch)}
                run.flops_per_solution[m.template] = flops.total(
                    run.parts[m.template][1])
            if on_chip:
                run.trace = _reduce_trace(run, trace_dir, log, win["t0"],
                                          t_end)
                device["busy_s"] = run.trace["busy_s"]
                device["window_s"] = run.trace["window_s"]
            else:
                note("off the chip: the trace is not read, no device "
                     "metric is printed")

        # ---- metrics ---------------------------------------------------
        metrics = {}
        if args.trace:
            for m in cell.per_layer():
                if not on_chip and m["source"] != "program_counter":
                    continue  # off the chip only counts are printed
                value = cell.reader(m["name"])(run)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        elif on_chip:
            for m in cell.end_to_end():
                if m["name"] == "setup_s":
                    value = run.setup_s
                else:
                    value = cell.reader(m["name"])(run)
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        # An untraced line says where a lost tick went, under a key the
        # driver does not read: each tick's seconds, and what the readers
        # of the program's own spans and counts give over the whole window
        # (no device metric: the trace is not taken; off the chip only
        # counts, as in the traced line)
        detail = None
        if not args.trace:
            detail = {"tick_s": win["tick_s"]}
            for m in cell.per_layer():
                if m["source"] == "program_counter" or (
                        on_chip and m["source"] == "program_span"):
                    value = cell.reader(m["name"])(run)
                    if value is not None:
                        detail[m["name"]] = value

        # ---- correct: the reference has the chip now --------------------
        sysm.free_program()
        compared = {"chain_mismatch": {"value": bad, "limit": 0}}
        t_ref = time.perf_counter()
        worst: dict[str, dict] = {}
        for rec in chosen:
            if rec["taskid"] not in served:
                continue  # nothing decodable: a chain_mismatch already
            m = sysm.model(rec["model"])
            stats = m.family.compare(m, rec, served[rec["taskid"]],
                                     control=args.control)
            mine = worst.setdefault(rec["model"], {})
            for name in m.family.COMPARED:
                value = stats[name]["value"]
                mine[name] = max(mine.get(name, value), value)
            note(f"reference: {rec['model']} task "
                 f"0x{rec['taskid'].hex()[:8]} "
                 + " ".join(f"{n} {v['value']:.4f}"
                            for n, v in stats.items())
                 + f" ({time.perf_counter() - t_ref:.0f}s in) "
                 f"{json.dumps(stats)}")
        for model, mine in sorted(worst.items()):
            limits = sysm.model(model).entry["limits"]
            for name, value in mine.items():
                compared[f"{name}.{model}"] = {"value": value,
                                               "limit": limits[name]}
        missing = [m for m in per_model if m not in worst and per_model[m]]
        ok = not missing and all(
            c["value"] <= c["limit"] for c in compared.values())
        result = {
            "correct": bool(ok), "attempted": len(win["tasks"]),
            "failed": len(win["tasks"]) - len(solved),
            "metrics": metrics, "device": device,
        }
        if run.trace is not None:
            result["breakdown"] = run.trace["breakdown"]
        result.update(
            workload=cell.name, seed=args.seed, trace=args.trace,
            control=args.control, window_s=window_s, ticks=win["ticks"],
            solved=len(solved), reference_s=time.perf_counter() - t_ref,
            compile_cache={"setup": warm_cache,
                           "lookups_in_window": compiled_in_window},
            timings=run.timings)
        if run.trace is not None:
            result["trace_lines"] = run.trace["lines"]
            result["trace_aligned_by"] = run.trace["aligned_by"]
        if missing:
            result["not_compared"] = missing
        if detail is not None:
            result["window_detail"] = detail
        result["compared"] = compared
        for name, c in compared.items():
            print(f"compared {name}: {c['value']:.6g} (limit {c['limit']:g})"
                  + ("" if c["value"] <= c["limit"] else "  <-- over"),
                  file=sys.stderr, flush=True)
        if missing:
            print(f"compared: no finished task of {missing} to compare",
                  file=sys.stderr, flush=True)
        return 0, result
    finally:
        sysm.close()
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)


def main(argv, t_start: float) -> int:
    args = parse(argv)
    code, result = run_cell(args, t_start)
    if result is not None:
        print(json.dumps(result), flush=True)
    return code
