"""Of the prompt tokens of the requests prefilled in the window, the share
the prefix cache supplied: the sum of ``first_tokens[].cached`` over the sum
of ``first_tokens[].prompt`` of the window's step records."""

from ._phases import records


def read(ctx):
    entries = [e for r in records(ctx) or () for e in r["first_tokens"]]
    prompt = sum(e["prompt"] for e in entries)
    return 100.0 * sum(e["cached"] for e in entries) / prompt \
        if prompt else None
