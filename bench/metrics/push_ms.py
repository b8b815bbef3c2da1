"""Host milliseconds per ``ServiceLoop.push`` call, averaged over the window."""


def read(ctx):
    push = ctx.get("push_s") or []
    return 1e3 * sum(push) / len(push) if push else None
