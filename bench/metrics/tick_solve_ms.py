"""Milliseconds the in-tick simplex solve adds: the mean ``serve.wait`` of the
ticks that proposed minus that of the ticks that held.  Inside the tick
program the solve has no name of its own in the device trace.  A borg run
proposes once per eight ticks, so the note gives how many of each it read."""
from bench.spans import mean_ms, snapshot, tick_waits


def read(ctx):
    snap = snapshot()
    if snap is None:
        return None
    hold, prop = tick_waits(snap)
    if not hold or not prop:
        return None
    ctx.setdefault("notes", []).append(
        f"tick_solve_ms over {len(prop)} proposing and {len(hold)} holding ticks")
    return mean_ms(prop) - mean_ms(hold)
