"""The serving plans' share of their memory roofline, in percent: the
time the profiled flushes' payload bytes need at the chip's HBM
bandwidth, over the device time of every op of the serving plans
(copies included).  Payload bytes are those the results require
(``yardstick.payload_bytes``), so the share reads the same work
whatever implements it; the operations are a few per point, so memory
bounds it."""


def read(record):
    dev, peaks = record["device"], record["peaks"]
    if not dev or not peaks or dev["busy_s"] <= 0:
        return None
    if dev["plan_op_s"] <= 0:
        # the device ran, but no op in a module the reduction takes for
        # a serving plan: the plans' jit name changed
        raise ValueError("plan_roofline: the trace holds device ops but "
                         "none of a serving plan's module")
    need = record["traced_payload_bytes"] / peaks["hbm_bw"]
    return 100.0 * need / dev["plan_op_s"]
