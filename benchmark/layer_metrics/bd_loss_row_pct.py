"""Share of the window's data tokens whose position carried a loss: the
program's ``bd_loss_rows`` counter (positions with a weight above 0) over
``toks`` on the window's ``step_window`` events. About half under a rate drawn
uniformly a block; a draw that masks nothing or everything shows here."""

from _blockdiff import window_events


def read(sources):
    events = window_events(sources, "bd_loss_rows", "toks")
    toks = sum(int(e["toks"]) for e in events)
    if not toks:
        return None
    return 100.0 * sum(int(e["bd_loss_rows"]) for e in events) / toks
