"""Speculative decode: of the drafts the bucket programs' loops verified
(a multi-token prediction module's guess at the token after next, run
through the main model beside the token before it), the share that WAS
the sampler's choice and so yielded a second token in the step — the
program's own int32 counts, which the text runner's finalize puts on
`text.speculate` spans (`drafts`, `accepted`). Source: the program's obs
journal; a count. With weights drawn from a seed it reads what chance
gives over the ids the answer visits; a trained module reads 85-90. A
program without such spans returns nothing."""
from perfbench.spans import named


def read(run):
    drafts = accepted = 0
    for s in named(run.spans, "text.speculate"):
        drafts += s["attrs"].get("drafts", 0)
        accepted += s["attrs"].get("accepted", 0)
    if not drafts:
        return None
    return 100.0 * accepted / drafts
