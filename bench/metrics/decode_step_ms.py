"""Host-clock time of the fused decode dispatches in the window, per decode
step (each dispatch ends blocked on its tokens)."""


def read(rec):
    if not rec.decode_steps:
        return None
    return 1e3 * rec.span_s("decode") / rec.decode_steps
