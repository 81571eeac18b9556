"""Share of the prompt tokens of turns admitted in the window that the
allocator served from its prefix cache (a program counter)."""


def read(rec):
    prompt = rec["admitted_prompt_tokens"]
    if not prompt:
        return None
    return 100.0 * rec["admitted_cached_tokens"] / prompt
