"""Requests served per batch slot offered in the window, in %, from the
program's counters ``serve.requests_served`` and ``serve.batches``."""


def read(run):
    batches = run.obs_delta.get("serve.batches", 0)
    if not batches:
        return None
    served = run.obs_delta.get("serve.requests_served", 0)
    return 100.0 * served / (batches * run.config["batch_slots"])
