"""Engine: host microseconds the engine spends per intercepted syscall
on the resumes' sessions, walking the graph to pre-issue (peek) and
handing results back (harvest), from the Foreactor counters over the
window's resumes."""


def read(ctx):
    s = ctx.stats.get("fa_delta") or {}
    if not s.get("intercepted"):
        return None
    return 1e6 * (s.get("peek_seconds", 0.0) + s.get("harvest_seconds", 0.0)) \
        / s["intercepted"]
