"""The one traffic generator.  A traffic mix is a JSON file of parameters
(`traffic/<mix>.json`); this module makes its inputs from the seed and
drives the forward with them in a closed loop.

Parameters of a mix:

  batch          images per request
  ring           distinct requests, made from the seed in set-up and
                 replayed in order (request i sends ring[i % ring])
  host_io        false: the ring lives on the device, requests are
                 dispatched back to back (async), and the host waits only
                 when more than ``in_flight`` are outstanding; the window
                 ends once all are drained.
                 true: each request starts from a host numpy image, goes
                 through ``device_put``, the forward, and its logits back
                 to the host, and is timed on the host clock from the
                 first to the last
  in_flight      outstanding requests allowed (host_io false)
  warmup         requests sent in set-up, before the window
  trace_seconds  the window's length in a traced run
"""

from __future__ import annotations

import collections
import time

import jax
import jax.numpy as jnp
import numpy as np

span = jax.profiler.TraceAnnotation


def make_ring(key, traffic: dict, image_size: int, channels: int) -> list:
    """``ring`` distinct requests of N(0, 1) pixels (normalised images),
    made on the device in one call; host numpy arrays where the mix
    starts from the host."""
    shape = (traffic["ring"], traffic["batch"], image_size, image_size,
             channels)
    ring = jax.jit(lambda k: tuple(jax.random.normal(k, shape, jnp.float32))
                   )(key)
    if traffic["host_io"]:
        return [np.asarray(x) for x in ring]
    return list(ring)


def drive(step, ring: list, traffic: dict, seconds: float,
          max_requests: int | None = None) -> dict:
    """Send requests 0, 1, ... until ``seconds`` have passed since the first
    was sent (or ``max_requests`` were sent); return what they produced.

    ``outputs[i]`` answers request i, which sent ``ring[i % len(ring)]``;
    ``latencies_s`` has one entry per request where the mix times requests
    (host_io)."""
    n_ring = len(ring)
    limit = float("inf") if max_requests is None else max_requests
    outputs, latencies = [], []
    i = 0
    t0 = time.perf_counter()
    if traffic["host_io"]:
        while i < limit and time.perf_counter() - t0 < seconds:
            ts = time.perf_counter()
            with span("h2d"):
                x = jax.device_put(ring[i % n_ring])
            with span("dispatch"):
                y = step(x)
            with span("fetch"):
                y = np.asarray(y)
            latencies.append(time.perf_counter() - ts)
            outputs.append(y)
            i += 1
    else:
        pending = collections.deque()
        while i < limit and time.perf_counter() - t0 < seconds:
            with span("dispatch"):
                y = step(ring[i % n_ring])
            outputs.append(y)
            pending.append(y)
            i += 1
            if len(pending) > traffic["in_flight"]:
                with span("drain"):
                    pending.popleft().block_until_ready()
        with span("drain"):
            for y in pending:
                y.block_until_ready()
    elapsed = time.perf_counter() - t0
    return {"requests": i, "images": i * traffic["batch"],
            "elapsed_s": elapsed, "latencies_s": latencies,
            "outputs": outputs, "slots": [k % n_ring for k in range(i)]}


def warm_up(step, ring: list, traffic: dict) -> None:
    """Send the mix's ``warmup`` requests through the window's own path."""
    drive(step, ring, traffic, float("inf"), max_requests=traffic["warmup"])
