"""Denoising steps of the requests completed in the window over the span
from the first request's start to the last completed one's finish."""


def read(run):
    done = run.completed
    return sum(r["steps"] for r in done) / (done[-1]["finish"]
                                            - run.records[0]["due"])
