"""batch_occupancy: mean share of the engine's slots that the window's
decode steps ran, over the steps that dispatched one, in percent.
Program counter (``step()``'s return); traced run only."""


def read(rec):
    return rec["occupancy_pct"]
