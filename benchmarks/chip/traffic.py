"""The one traffic generator: a mix file's parameters in, query batches out.

A mix (``mixes/<name>.json``) says how the system is loaded.  The keys
this file reads:

``source``        where the mix comes from (text).
``loop``          the name of a file ``loops/<loop>.py`` that drives the
                  window (``drive``); the mix's other top-level keys are
                  that loop's parameters, and the loop refuses any it
                  does not implement.
``batch``         queries per request.
``pool_batches``  distinct batches made in set-up; the window cycles them.
``keys``          ``{"draw": <name>, ...}``: the file ``draws/<name>.py``
                  that places each query in the table (``positions``),
                  with its parameters.

A later mix with another loop or key draw adds that file; every key a
mix states is read by some file or refused, never ignored.  The same
mix, table and seed give the same batches in every process.
"""

from __future__ import annotations

import numpy as np

#: independent random streams drawn from one ``--seed``
STREAM_TRAFFIC = 1
STREAM_SAMPLE = 2

MIX_KEYS = ("source", "loop", "batch", "pool_batches", "keys")


def loop_params(mix: dict) -> dict:
    return {k: v for k, v in mix.items() if k not in MIX_KEYS}


def draw_params(mix: dict) -> dict:
    return {k: v for k, v in mix["keys"].items() if k != "draw"}


def problems(mix: dict, loop, draw) -> list:
    """What is wrong with ``mix`` for its ``loop`` and key ``draw`` modules."""
    out = [f"mix needs {k!r}" for k in MIX_KEYS[1:] if k not in mix]
    if out:
        return out
    for k in ("batch", "pool_batches"):
        if not isinstance(mix[k], int) or mix[k] < 1:
            out.append(f"mix: {k} must be a whole number of at least 1")
    return out + loop.problems(loop_params(mix)) + draw.problems(draw_params(mix))


def pool(mix: dict, draw, table: np.ndarray, seed: int) -> list:
    """The mix's ``pool_batches`` query batches (uint64 host arrays)."""
    rng = np.random.default_rng([seed, STREAM_TRAFFIC])
    params = draw_params(mix)
    return [
        table[draw.positions(rng, params, len(table), int(mix["batch"]))]
        for _ in range(int(mix["pool_batches"]))
    ]
