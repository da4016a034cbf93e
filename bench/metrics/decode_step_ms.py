"""Device time of one decode step, in ms: the summed executions of the
engine's decode program in the trace over their number."""

PROGRAM = r"^jit_decode_step$"


def read(rd):
    tr = rd.get("trace")
    if tr is None:
        return None
    secs, calls = tr.programs(PROGRAM)
    return secs / calls * 1e3 if calls else None
