"""pipelined executor: ``batch_done.result.h2d_bytes`` of the jterator step
— the host arrays handed to the device by the first launch and by every
re-launch of the escalation loop — summed, in MB (1e6 bytes) over sites."""

from benchmark import ledger

UNIT = "MB/site"


def read(run):
    if run.kind != "plate" or not run.units:
        return None
    results = ledger.batch_results(run.events(), "jterator")
    if not any("h2d_bytes" in r for r in results):
        return None
    return sum(int(r.get("h2d_bytes", 0)) for r in results) / 1e6 / run.sites
