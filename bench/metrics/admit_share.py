"""Share of the window spent in the engine's admission calls (prefill of
the trunk and replay of the prompt tail; each call ends blocked on its
results), by the host clock."""


def read(rec):
    return 100.0 * rec.span_s("admit") / rec.window_s
