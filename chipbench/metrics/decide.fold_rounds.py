"""Update rounds per dispatched batch in the window: the delta of the
server's ``asa_serve_fold_rounds_total`` counter over the delta of
``asa_serve_batches_total``. 1 where no batch repeats an observing
tenant; None where the program has no such counter."""


def read(ctx):
    c = ctx.get("counters")
    key = "asa_serve_fold_rounds_total"
    if c is None or key not in c[1]:
        return None
    before, after = c
    batches = after["asa_serve_batches_total"] - \
        before["asa_serve_batches_total"]
    return (after[key] - before[key]) / batches if batches else None
