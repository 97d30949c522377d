"""Engine: share of intercepted syscalls served from a speculated
(pre-issued) request, from the Foreactor counters over the window's
resumes (``served_async / intercepted``)."""


def read(ctx):
    s = ctx.stats.get("fa_delta") or {}
    if not s.get("intercepted"):
        return None
    return 100.0 * s["served_async"] / s["intercepted"]
