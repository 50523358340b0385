"""Share of the rows the grouped expert kernels computed in the traced
steps that were block padding: 1 - ``moe_held_rows`` / ``moe_padded_rows``
(the program's counters, over the window)."""


def read(r):
    c = r["counters_window"]
    padded = c.get("moe_padded_rows")
    if not padded:
        return None
    return 100.0 * (1.0 - c.get("moe_held_rows", 0.0) / padded)
