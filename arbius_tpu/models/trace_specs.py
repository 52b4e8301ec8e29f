"""Trace-spec registry — the model zoo's jittable entry points, enumerable.

graphlint (`arbius_tpu/analysis/graph`) audits COMPILED programs, not
Python source; for that it needs a durable answer to "what XLA programs
does this repo ship?". Each pipeline module answers with a
`trace_specs()` function returning `TraceSpec`s: a (model, entry,
shape-bucket, mesh layout, dtype) identity plus a `build()` thunk that
produces the jittable callable and abstract (ShapeDtypeStruct) example
arguments — everything `jax.make_jaxpr` needs, nothing concrete, so a
full-registry trace runs on a CPU-only host in seconds and never
allocates model weights (params come from `jax.eval_shape` over the
pipeline's own init).

Specs use the tiny test configs: the *topology* of the traced graph —
primitive mix, dtype discipline, reduction order, PRNG threading — is
what the GRAPH4xx rules and the golden fingerprints pin, and those
properties are identical between the tiny and full builds of the same
pipeline code. What tiny shapes cannot stand in for (weights, exact
bits) is covered by the recorded golden CIDs in `goldens/` instead.

The spec `key` doubles as the golden filename stem in `goldens/graph/`,
so it must stay filename-safe and stable across releases: renaming a
key IS a fingerprint-history reset for that program.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Callable

_KEY_PART = re.compile(r"^[a-z0-9][a-z0-9_\-x.]*$")


@dataclass(frozen=True)
class TraceSpec:
    """One jittable entry point at one (bucket, mesh, dtype) identity.

    `build()` returns `(fn, args)` where `fn` is the jit-wrapped
    callable and `args` are abstract values (`jax.ShapeDtypeStruct`
    trees) — callers trace with `jax.make_jaxpr(fn)(*args)`.

    `allow` carries spec-level waivers with the same semantics as
    detlint's `# detlint: allow[RULE] reason` pragmas: each entry is
    `(rule_id, reason)`, the reason is mandatory, and waivers apply
    only to GRAPH4xx rule findings — fingerprint mismatches (GRAPH49x)
    can never be waived.
    """

    model: str   # template name, e.g. "anythingv3"
    entry: str   # entry point, e.g. "txt2img"
    bucket: str  # shape bucket tag, e.g. "b1.64x64.ddim.s2"
    mesh: str    # mesh layout tag: "single" or e.g. "dp2.sp2.tp2"
    dtype: str   # compute dtype of the spec, e.g. "bfloat16"
    build: Callable[[], tuple]
    allow: tuple = field(default=())

    @property
    def key(self) -> str:
        return f"{self.model}.{self.entry}.{self.bucket}.{self.mesh}.{self.dtype}"

    def waiver(self, rule_id: str) -> str | None:
        """Reason string if `rule_id` is waived for this spec, else None
        (a reasonless waiver waives nothing, like a reasonless pragma)."""
        for rid, reason in self.allow:
            if rid == rule_id and reason:
                return reason
        return None


def validate_specs(specs: list[TraceSpec]) -> list[TraceSpec]:
    """Shared registry hygiene: unique filename-safe keys, justified
    waivers. Returns the specs sorted by key (stable audit order)."""
    seen: dict[str, TraceSpec] = {}
    for s in specs:
        for part in (s.model, s.entry, s.bucket, s.mesh, s.dtype):
            if not _KEY_PART.match(part):
                raise ValueError(
                    f"trace spec {s.key!r}: part {part!r} is not "
                    "filename-safe ([a-z0-9_.x-])")
        if s.key in seen:
            raise ValueError(f"duplicate trace spec key {s.key!r}")
        for entry in s.allow:
            if len(entry) != 2 or not entry[1].strip():
                raise ValueError(
                    f"trace spec {s.key!r}: waiver {entry!r} needs "
                    "(rule_id, reason) with a non-empty reason")
        seen[s.key] = s
    return [seen[k] for k in sorted(seen)]


def all_trace_specs() -> list[TraceSpec]:
    """Every registered pipeline's trace specs, validated and sorted.

    Imports are deferred so that enumerating the registry is the only
    time the model zoo is pulled in — the analysis CLI stays importable
    without jax/flax side effects until it actually audits.
    """
    from arbius_tpu.models.deepseek_v32 import pipeline as dsv32_pipeline
    from arbius_tpu.models.dots3 import pipeline as dots3_pipeline
    from arbius_tpu.models.joyai_flash import pipeline as joyai_pipeline
    from arbius_tpu.models.kandinsky2 import pipeline as kandinsky2_pipeline
    from arbius_tpu.models.nemotron_h import pipeline as nemotron_pipeline
    from arbius_tpu.models.rvm import pipeline as rvm_pipeline
    from arbius_tpu.models.sd15 import pipeline as sd15_pipeline
    from arbius_tpu.models.textgen import pipeline as textgen_pipeline
    from arbius_tpu.models.trinity import pipeline as trinity_pipeline
    from arbius_tpu.models.video import pipeline as video_pipeline
    from arbius_tpu.parallel import meshsolve

    specs: list[TraceSpec] = []
    for mod in (sd15_pipeline, kandinsky2_pipeline, rvm_pipeline,
                video_pipeline, textgen_pipeline, trinity_pipeline,
                dsv32_pipeline, joyai_pipeline, dots3_pipeline,
                nemotron_pipeline, meshsolve):
        specs.extend(mod.trace_specs())
    return validate_specs(specs)
