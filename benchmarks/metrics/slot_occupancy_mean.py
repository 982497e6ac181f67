"""Mean number of occupied slots over the scheduling iterations of the
window (the server's record_occupancy, raw samples)."""


def read(ctx):
    occ = [n for t, n in ctx["recorder"].occupancy
           if ctx["t_start"] <= t < ctx["t_end"]]
    return sum(occ) / len(occ) if occ else None
