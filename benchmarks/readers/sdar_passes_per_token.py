"""Row-passes a token that left: the block server's denoising and
commit passes (``ticks.totals``' ``passes`` + ``commits``, between the
window's two ends) over the tokens its rows emitted (``dc``).  A block
of 4 at 4 passes and a commit reads 1.25; a prompt's remainder and a
budget that ends inside a block add a little."""


def read(obs: dict, args: dict):
    totals = obs.get("window_totals")
    if not totals or not totals.get("dc") or "passes" not in totals:
        return None
    return (totals["passes"] + totals["commits"]) / totals["dc"]
