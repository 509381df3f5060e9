"""Tokens of every block update completed in the window, over the window
(host clock; the window closes when its last update has completed)."""


def read(run):
    if "tokens" not in run.data or run.window_s <= 0:
        return None
    return run.data["tokens"] / run.window_s
