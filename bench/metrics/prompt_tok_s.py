"""Prompt tokens prefilled inside the window per second of it."""
from bench.stats import prompt_tokens


def read(rec):
    return prompt_tokens(rec) / (rec.t1 - rec.t0)
