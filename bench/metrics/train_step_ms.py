"""Device time of one train step, in ms: the summed executions of the
step program (``build_train_step``'s ``step``) in the trace over their
number."""

PROGRAM = r"^jit_step$"


def read(rd):
    tr = rd.get("trace")
    if tr is None:
        return None
    secs, calls = tr.programs(PROGRAM)
    return secs / calls * 1e3 if calls else None
