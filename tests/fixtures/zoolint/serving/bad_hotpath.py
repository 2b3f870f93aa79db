"""Seeded wallclock-hotpath and hotpath-host-sync violations.

Lives under a ``serving/`` path segment so zoolint classifies it as a
hot-path module. Never imported — fixture data for dev/run-tests.sh
zoolint and tests/test_zoolint.py.
"""

import time

import jax
import numpy as np


def dispatch_loop(batches, fences):
    t0 = time.time()  # VIOLATION wallclock-hotpath
    total = 0.0
    for batch in batches:  # VIOLATION hotpath-host-sync (x3 below)
        total += float(batch.loss)
        total += batch.loss.item()
        jax.block_until_ready(fences)
    host = [np.asarray(b) for b in batches]  # VIOLATION hotpath-host-sync
    return total, host, time.time() - t0  # VIOLATION wallclock-hotpath


def dispatch_sampled(batches, sampled):
    """Suppressions must keep this half clean; a sampling guard alone no
    longer excuses a fence."""
    t0 = time.time()  # zoolint: disable=wallclock-hotpath
    for batch in batches:
        if sampled:
            jax.block_until_ready(batch)  # zoolint: disable=hotpath-host-sync
    return time.time() - t0  # zoolint: disable=wallclock-hotpath
