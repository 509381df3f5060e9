"""Output tokens delivered inside the window, over the window (host
clock)."""


def read(run):
    if "window_tokens" not in run.data or run.window_s <= 0:
        return None
    return run.data["window_tokens"] / run.window_s
