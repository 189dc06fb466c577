"""query execution: percent of the window's ``job_done`` events whose
``cache`` is ``hit`` (the digest-keyed result cache)."""

UNIT = "%"


def read(run):
    if run.kind != "serve":
        return None
    done = [e for e in run.window_events()
            if e.get("event") == "job_done" and e.get("kind") == "query"]
    if not done:
        return None
    return 100.0 * sum(1 for e in done if e.get("cache") == "hit") / len(done)
