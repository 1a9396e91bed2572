"""Share of the attention's tile grid that the block-diffusion plan visits: the
program's ``bd_tiles_live`` over ``bd_tiles_grid`` (a forward call's tiles a
head, from the plan that was traced) on the window's ``step_window`` events.
28.125% at 512 x 512 tiles over two copies of 8,192: the mask's exact live set;
100 would be a mask program run over the whole grid."""

from _blockdiff import window_events


def read(sources):
    events = [e for e in window_events(sources, "bd_tiles_live", "bd_tiles_grid")
              if int(e["bd_tiles_grid"]) > 0]
    if not events:
        return None
    return 100.0 * int(events[-1]["bd_tiles_live"]) / int(events[-1]["bd_tiles_grid"])
