"""Over the pure decode steps (``_phases``' own: no admission, no stall,
occupancy > 0): the sum of ``kv_rows_live`` over the sum of
``kv_rows_read``.  Both are the decode program's own counters on the step
record, of a model with window layers only (``paged.KV_KEYS``):
``kv_rows_read`` the rows of K the step's gathers brought in, summed over
layers and slots (every slot's whole table: the page table's width on a
whole-length layer, the ring's on a window layer), ``kv_rows_live`` the rows
a query could see (``len + 1``, on a window layer at most the window; none
in an empty slot).  The share of what a decode step gathered that any query
could use; the rest is the price of static shapes (ROADMAP S4b).  A program
that predates the counters, or a model without window layers, writes none
and reads nothing."""

from ._phases import records


def read(ctx):
    pure = [r for r in records(ctx) or ()
            if r["stall_s"] == 0 and r["admitted"] == 0 and r["occupancy"]
            and r.get("kv_rows_read")]
    if not pure:
        return None
    return 100.0 * sum(r["kv_rows_live"] for r in pure) \
        / sum(r["kv_rows_read"] for r in pure)
