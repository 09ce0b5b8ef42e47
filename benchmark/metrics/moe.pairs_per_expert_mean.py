"""Mean (token, expert) pairs a held expert receives in one expert-layer
call, over the window (``moe.expert_load`` counted by the program, read
after every tick): the rows each expert's matrices are multiplied with.
At 1 the grouped product streams a whole expert for one row; the weights'
stream is amortised as this grows."""


def read(run):
    counted = (run.get("counters") or {}).get("window")
    if not counted or not counted["layer_calls"]:
        return None
    held = counted["pairs"].shape[-1]
    return float(counted["pairs"].sum()) / (counted["layer_calls"] * held)
