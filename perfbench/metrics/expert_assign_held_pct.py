"""Expert layer: of the (token, choice) assignments the bucket programs'
routers made, the share that fell on experts held on this chip — the
program's own int32 sums, which the text runner's finalize puts on
`text.routed` spans (`assignments`, `held`). Source: the program's obs
journal; a count. The reference's FLOP count takes the expected share,
experts held over experts. A program without such spans returns nothing."""
from perfbench.spans import named


def read(run):
    made = held = 0
    for s in named(run.spans, "text.routed"):
        made += s["attrs"].get("assignments", 0)
        held += s["attrs"].get("held", 0)
    if not made:
        return None
    return 100.0 * held / made
