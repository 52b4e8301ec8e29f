"""Named blocks of the bucket programs, read back off the compiled HLO.

Every bucket program wraps its blocks in `jax.named_scope(<block>)`
(docs/observability.md "Blocks" lists them by family). A scope is
metadata: it enters the name stack each operation carries into the
optimized HLO as `metadata={op_name="jit(run)/…/prefill/…"}`, and
changes no equation, no parameter, no operation the compiler emits. The
profiler names a device operation by its HLO instruction (`fusion.12`),
unique within the module, so a map from instruction name to the blocks
on its `op_name` path is what puts a traced operation in its block.

`block_map(hlo_text)` builds that map from `Compiled.as_text()`;
`Obs.blocks(tag)` (obs/__init__.py) builds it on demand for a bucket
executable the node has run. An operation's blocks are those of its own
`op_name`; where XLA left it none (an instruction it made itself, a
fusion whose root it made), those its fused instructions share; where
that gives none either, those of the loop, branch or call that runs it.
An operation of the entry computation with none of the three is
unblocked: `()`.
"""
from __future__ import annotations

import re

# the fixed vocabulary, by the family prefix of the bucket tag; each name
# is a whole path component, none of jax's own (`jit(…)`, `while`,
# `body`, `cond`, `closed_call`) and no flax module's name outside the
# scope of the same name
VOCABULARY: dict[str, tuple[str, ...]] = {
    "textgen": ("prefill", "decode"),
    "trinity": ("prefill", "decode", "attention", "routed_experts"),
    "deepseek_v32": ("prefill", "decode", "attention", "routed_experts",
                     "indexer"),
    "joyai_llm_flash": ("prefill", "decode", "attention", "routed_experts",
                        "draft"),
    "dots3_note": ("prefill", "decode", "attention", "window_attention",
                   "routed_experts", "indexer"),
    "nemotron_h": ("prefill", "decode", "attention", "mamba_mixer",
                   "latent_moe", "routed_experts", "draft"),
    "kandinsky2": ("text_tower", "prior", "unet", "movq"),
    "sd15": ("text_encoder", "unet", "vae"),
}
BLOCKS: frozenset = frozenset(b for v in VOCABULARY.values() for b in v)

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'\bop_name="((?:[^"\\]|\\.)*)"')
_COMPUTATION = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+) .*\{\s*$")
# the computations whose instructions run as operations of their own: a
# loop's body and condition, a conditional's branches, a call's callee
# (not a fusion's body, which its fusion runs as one operation, nor a
# reduction's or a sort's comparator)
_RUNS = re.compile(r"\b(?:body|condition|true_computation|false_computation)"
                   r"=%?([\w.\-]+)|\bbranch_computations=\{([^}]*)\}")
_CALL = re.compile(r"\bcall\(.*\bto_apply=%?([\w.\-]+)")
_FUSION = re.compile(r"\bfusion\(.*\bcalls=%?([\w.\-]+)")


def block_path(op_name: str, vocabulary=BLOCKS) -> tuple[str, ...]:
    """The vocabulary's names on an `op_name` path, outermost first,
    each once."""
    out: list[str] = []
    for part in op_name.split("/"):
        if part in vocabulary and part not in out:
            out.append(part)
    return tuple(out)


def _shared(paths: list[tuple]) -> tuple:
    """The blocks every path of `paths` holds, in the first's order."""
    if not paths:
        return ()
    return tuple(b for b in paths[0] if all(b in p for p in paths[1:]))


def block_map(hlo_text: str, vocabulary=BLOCKS) -> dict[str, tuple]:
    """{instruction name: (blocks, outermost first)} over the
    instructions that run as operations: those of the entry computation
    and of the loops, branches and calls it reaches (module docstring
    for where an operation's blocks come from)."""
    # computation → [(instruction, own blocks or None, computations it
    # runs, the fused computation of a fusion)]
    comps: dict[str, list] = {}
    entry = current = None
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m is None:
            head = _COMPUTATION.match(line)
            if head is not None:
                current = head.group(2)
                comps[current] = []
                if head.group(1):
                    entry = current
            continue
        if current is None:
            continue
        op = _OP_NAME.search(line, m.end())
        own = block_path(op.group(1), vocabulary) if op is not None else None
        runs = []
        for r in _RUNS.finditer(line):
            runs.extend([r.group(1)] if r.group(1) else
                        (c.strip().lstrip("%") for c in r.group(2).split(",")))
        c = _CALL.search(line)
        if c is not None:
            runs.append(c.group(1))
        f = _FUSION.search(line)
        comps[current].append((m.group(1), own, runs,
                               f.group(1) if f is not None else None))

    def fused(name):
        return _shared([own for _, own, _, _ in comps.get(name, ())
                        if own is not None])

    out: dict[str, tuple] = {}
    todo, seen = [(entry, ())], set()
    while todo:
        name, context = todo.pop()
        if name not in comps or name in seen:
            continue
        seen.add(name)
        for instr, own, runs, body in comps[name]:
            path = own or (fused(body) if body else ()) or context
            out[instr] = path
            todo.extend((r, path) for r in runs)
    return out


def block_counts(bmap: dict[str, tuple]) -> dict[str, int]:
    """Instructions of a map per block (an instruction counts for each
    block on its path), and those with none under `unblocked`."""
    counts: dict[str, int] = {}
    for path in bmap.values():
        for b in path or ("unblocked",):
            counts[b] = counts.get(b, 0) + 1
    return dict(sorted(counts.items()))
