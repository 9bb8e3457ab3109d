"""Output tokens stamped in the window / window seconds: the part of
`serve_tokens_per_s` a reader of the answers sees (ISSUE 27's first
definition of it).  It swings with how much of the window went to
prefilling the requests that came in (PERF.md section 2), so it stands
here without a bound."""


def read(record):
    work = record.get("work")
    if not work or not work["output_tokens"]:
        return None
    return work["output_tokens"] / record["window_s"]
