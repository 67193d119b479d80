"""Share of the prompt tokens that the measured engine took into its cache
in prompt chunks, in percent, from the engine's own record: the ticks'
``prefill_tokens`` over their ``prefill_tokens`` plus ``replay_tokens``
(prompt tokens fed one a tick through decode rows).  A dense-attention
configuration whose prompts fit in the cache takes every prompt in chunks
and reads 100; the time to first token then grows with a prompt's chunks,
not with its tokens.  A record without these columns reads nothing.
"""

from bench import engine_record


def read(ctx):
    snap = engine_record.snapshot()
    if snap is None:
        return None
    ticks = snap["ticks"]
    if "prefill_tokens" not in ticks or "replay_tokens" not in ticks:
        return None
    chunked = sum(ticks["prefill_tokens"])
    total = chunked + sum(ticks["replay_tokens"])
    return 100.0 * chunked / total if total else None
