"""The arithmetic of `correct`: what is compared and how a gap is read.
The limits live in each cell's file; the readings they were set from are
in PERF.md.
"""
from __future__ import annotations

import json


def served(gaps: list, limits: dict, extra: dict, say=print) -> list:
    """`gaps`: for every sampled served token, how far its logit lies
    below the reference's best at that position."""
    say(json.dumps({"check": dict(extra, tokens_compared=len(gaps))}))
    return [{"name": "served_logit_widest_gap",
             "value": max(gaps) if gaps else 1e30,   # nothing to compare
             "limit": limits["served_logit_gap"]}]
