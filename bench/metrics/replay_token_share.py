"""Share of the prompt tokens admitted in the window that the engine fed
through decode-shaped replay steps (counted from the tails it was given)."""


def read(rec):
    if not rec.prompt_tokens:
        return None
    return 100.0 * rec.replay_tokens / rec.prompt_tokens
