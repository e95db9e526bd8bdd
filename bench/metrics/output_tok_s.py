"""Tokens served per second of the window: every token the window's
decode steps and prefills produced, over the window's length."""


def read(run):
    n = run.tokens_in_window()
    return n / run.window_s if n else None
