"""Serving. Median of the program's ``queue_wait`` span (cat ``serve``):
from a request's enqueue to the start of the batch that took it."""


def read(obs):
    return obs.spans.median_ms("queue_wait", cat="serve")
