"""Share of the window's served invocations whose program was enqueued
while an earlier program of the node was still unfinished on the device
(``InvocationRecord.launch_overlapped``), in percent. A program whose
records lack the field gives no value, and the metric is left out of the
result line."""
from readers import share


def read(run):
    recs = [i.record for i in run.served]
    if not any(hasattr(r, "launch_overlapped") for r in recs):
        return None
    overlapped = sum(bool(getattr(r, "launch_overlapped", None)) for r in recs)
    return share(overlapped, len(recs))
