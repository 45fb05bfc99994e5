"""Offline backlog: before every step the queue is topped up to at
least ``slots`` requests, so the engine never waits for work."""
from jax.profiler import TraceAnnotation

from bench.drive import clock


def run(client) -> None:
    slots = client.traffic.slots
    while True:
        now = clock()
        if now >= client.w.end_mark:
            return
        client.trace_switch(now)
        with TraceAnnotation("bench.submit"):
            while client.queued() < slots:
                client.submit()
        client.step()
