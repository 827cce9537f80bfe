"""Mean over the pure decode steps (``_phases``' own: no admission, no
stall, occupancy > 0) of ``experts_hit`` over layers x experts: the share
of the expert weights a decode step had to read.  ``experts_hit`` is the
routed model's own counter on the step record (the experts, summed over the
layers, that got at least one token of a live slot); a dense model, or a
program that predates the counter, writes none and reads nothing."""

from ._phases import records


def read(ctx):
    model = ctx["model"]
    pure = [r["experts_hit"] for r in records(ctx) or ()
            if r["stall_s"] == 0 and r["admitted"] == 0 and r["occupancy"]
            and "experts_hit" in r]
    if not pure or "num_experts" not in model:
        return None
    whole = model["num_hidden_layers"] * model["num_experts"]
    return 100.0 * sum(pure) / (whole * len(pure))
