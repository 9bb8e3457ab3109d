"""What a span of time held of a served run, counted from the requests'
own stamps: the basis of the serving cells' token, operation and byte
counts.

Output tokens are counted at their stamps (`Request.t_tokens`).  Prompt
tokens are counted chunk by chunk, as the engine prefills them: it runs
the chunks of one prompt at a time, oldest admission first, one chunk a
scheduler iteration, and stamps the first token when the last chunk ends.
So a prompt's `ceil(P / chunk)` chunks end evenly spaced between the later
of its admission and the previous prompt's first token, and its own first
token; the last chunk ends on that stamp exactly.  Only the one prompt
being prefilled at each edge of a span is placed by this interpolation, to
within a chunk.
"""
from __future__ import annotations


def chunk_ends(requests: list, chunk: int) -> list:
    """`requests`: `[(prompt length, admission time, token stamps)]`.
    Returns `[(end time, first position, end position)]` for every chunk
    of every prompt whose first token is stamped, in the engine's order."""
    out, prev_first = [], None
    for P, t_admit, stamps in sorted(
            (r for r in requests if r[1] is not None and r[2]),
            key=lambda r: (r[1], r[2][0])):
        first = stamps[0]
        start = t_admit if prev_first is None else max(t_admit, prev_first)
        start = min(start, first)
        n = -(-P // chunk)
        for c in range(n):
            out.append((start + (first - start) * (c + 1) / n,
                        c * chunk, min((c + 1) * chunk, P)))
        prev_first = first
    return out


def count_work(requests: list, lo: float, hi: float, chunk: int) -> dict:
    """What [lo, hi) held:
    output_tokens    tokens stamped;
    decode_tokens    those that a decode step yielded (every output but a
                     request's first, which its last chunk yields);
    decode_context   positions those attended, summed (P + i for output i);
    prompt_tokens    prompt tokens of the chunks that ended there;
    chunks           how many chunks ended there;
    chunk_context    the context at each such chunk's end, summed: what one
                     read of the keys and values a chunk needs;
    prefill_context  positions their prompt tokens attended, summed (token
                     q attends q + 1): what the attention's products need;
    gaps             between consecutive tokens, the later one inside."""
    out = dict(output_tokens=0, decode_tokens=0, decode_context=0,
               prompt_tokens=0, chunks=0, chunk_context=0,
               prefill_context=0, gaps=[])
    for P, _t_admit, stamps in requests:
        for i, t in enumerate(stamps):
            if lo <= t < hi:
                out["output_tokens"] += 1
                if i:
                    out["decode_tokens"] += 1
                    out["decode_context"] += P + i
                    out["gaps"].append(t - stamps[i - 1])
    for t, a, b in chunk_ends(requests, chunk):
        if lo <= t < hi:
            out["prompt_tokens"] += b - a
            out["chunks"] += 1
            out["chunk_context"] += b
            out["prefill_context"] += (b * (b + 1) - a * (a + 1)) // 2
    return out
