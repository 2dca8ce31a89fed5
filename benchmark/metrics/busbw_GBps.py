"""Bus bandwidth (nccl-tests' convention): the sum over completed calls
of 2(P-1)/P * bytes per rank, over the whole window's wall time (host
clock). None on one rank, where an allreduce moves nothing."""

import costs


def read(run):
    if run.world < 2 or not run.calls:
        return None
    moved = sum(costs.bus_bytes(size, run.world) for size, _, _ in run.calls)
    return moved / ((run.t_end - run.t_start) / 1e9) / 1e9
