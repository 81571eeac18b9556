"""Share of the chunk step's padded rows that carry no prompt token:
1 - prompt tokens computed by chunk steps / (chunk calls x slots x chunk).
From the engine's step plans (a program counter), over the window."""


def read(rec):
    calls = sum(1 for s in rec["steps"] if s["chunk"])
    if not calls:
        return None
    tokens = sum(n for s in rec["steps"] for _, n in s["chunk"])
    return 100.0 * (1.0 - tokens / (calls * rec["slots"] * rec["chunk"]))
