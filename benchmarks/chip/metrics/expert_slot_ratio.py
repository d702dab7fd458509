"""Engine: rows the prefills' grouped expert matmuls were given per row
routed to a held expert (the runner's ``expert_slots`` over its
``expert_rows``, summed over the window's shares): 1 when the grouped
matmuls are given only routed rows. Rows per held expert per expert layer
at level 0 go to stderr. None where the program keeps no such counter or
routes no row."""
import sys


def read(ctx):
    shares = [s for r in ctx.records for s in r.shares]
    if not shares or not all(hasattr(s, "expert_rows") for s in shares):
        return None
    rows = sum(s.expert_rows for s in shares)
    if rows <= 0:
        return None
    doc, lv = ctx.doc, ctx.doc["ladder"][0]
    level0 = [s for s in shares if s.level == 0]
    layers = lv["num_hidden_layers"] - doc.get("first_k_dense_replace", 0)
    if level0 and layers > 0 and doc.get("n_routed_experts"):
        per = sum(s.expert_rows for s in level0) / len(level0) / layers \
            / doc["n_routed_experts"]
        print(f"expert_slot_ratio: {per:.1f} rows per held expert per expert "
              "layer in a level-0 prefill share", file=sys.stderr)
    return sum(s.expert_slots for s in shares) / rows
