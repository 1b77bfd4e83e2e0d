"""Host transfers of the executor per sweep of the window, from the
program's own counter (``simulator.HOST_TRANSFER_COUNT``)."""


def read(ctx):
    w = ctx["window"]
    if not w["sweeps"]:
        return None
    return w["host_transfers"] / len(w["sweeps"])
