"""Every output token streamed in the window over the window's seconds
(host clock)."""


def read(rec):
    return rec["tokens"] / rec["window_s"]
