"""Mean time a served query waited in the frontend's queue, in milliseconds.

Read from the program's ``repro.frontend.flush`` spans (``serve/frontend.py``,
scheduler thread) that start inside ``bench.window``: the sum of their
``wait_sum_us`` ids (each request's flush time minus its enqueue time) over
the sum of their ``q`` ids.  None where no pass was flushed.
"""
from bench import spans


def read(run):
    got = spans.for_run(run)
    flushes = got.in_window("frontend.flush") if got is not None else []
    queries = sum(s["q"] for _, _, s in flushes)
    if not queries:
        return None
    return sum(s["wait_sum_us"] for _, _, s in flushes) / queries * 1e-3
