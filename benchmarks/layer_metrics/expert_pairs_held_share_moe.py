"""Over the pure decode steps (``_phases``' own: no admission, no stall,
occupancy > 0) of a program that holds a SHARE of its router's experts: the
(token, expert) pairs that landed on the held experts and went through the
grouped products, ``expert_pairs``, over the pairs the router made, here or
elsewhere, ``expert_pairs_routed`` (live slots x experts a token); sums over
the steps.  An even router gives held / router width (32 of 256: 12.5%); a
share above it with an even load is the program computing pairs of experts
it does not hold.  Both are the decode program's own counters on the step
record; a program that holds every expert writes no ``expert_pairs_routed``
and reads nothing."""

from ._phases import records


def read(ctx):
    pure = [r for r in records(ctx) or ()
            if r["stall_s"] == 0 and r["admitted"] == 0 and r["occupancy"]
            and "expert_pairs_routed" in r]
    routed = sum(r["expert_pairs_routed"] for r in pure)
    if not routed:
        return None
    return 100.0 * sum(r["expert_pairs"] for r in pure) / routed
