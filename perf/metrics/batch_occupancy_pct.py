"""Share of the decode lanes that yielded a token: decode steps' tokens in
the window over (`stats()["steps"]` in the window x `max_batch`).  Source:
the engine's counter and the requests' token stamps."""


def read(record):
    steps = record.get("steps")
    if not steps:
        return None
    return 100.0 * record["work"]["decode_tokens"] / (steps
                                                      * record["max_batch"])
