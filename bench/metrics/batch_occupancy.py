"""batch_occupancy: requests per engine batch over the measured window.

Counter of ``serve/graph_engine.py``: the change of ``dispatched`` over
the change of ``batches`` (summed over request kinds) between the
window's start and its close. It shows how much the micro-batcher
coalesces: one batch is one executor call, with its host planning and
device launches.
"""


def read(ctx):
    eng0, eng1 = ctx.engine
    batches = sum(eng1["batches"].values()) - sum(eng0["batches"].values())
    sent = sum(eng1["dispatched"].values()) - sum(eng0["dispatched"].values())
    return sent / batches if batches > 0 else None
