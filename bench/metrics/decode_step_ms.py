"""Device time per call of the engine's decode step, from the trace."""


def read(rec):
    prog = rec["trace"]["programs"]["decode_step"]
    if not prog["calls"]:
        return None
    return 1e3 * prog["seconds"] / prog["calls"]
