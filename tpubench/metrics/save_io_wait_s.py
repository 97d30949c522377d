"""Engine: seconds a background save's write session blocked on storage,
waiting for pre-issued requests or serving syscalls synchronously
(``wait_s + sync_s`` of the ``fa.session`` spans under its ``ckpt.write``),
per save."""

from bench import program_spans as ps


def read(ctx):
    spans = ps.window_spans(ctx)
    total, n = 0.0, 0
    for save in ps.handed_off(spans, "ckpt.save", "ckpt.save_async"):
        sessions = [f for w in ps.within(spans, save, "ckpt.write")
                    for f in ps.within(spans, w, "fa.session")]
        if not any("wait_s" in f.meta for f in sessions):
            continue
        total += sum(f.meta.get("wait_s", 0.0) + f.meta.get("sync_s", 0.0)
                     for f in sessions)
        n += 1
    return total / n if n else None
