"""The engine's round log over the WINDOW: the rows ``ServeMetrics`` keeps of
the engine's last calls (``hetu_tpu/serve/metrics.py``, ``ROUND_FIELDS``: one
row a decode round or prefill chunk, its five seams on
``time.monotonic_ns()``), cut to the calls that opened inside the untraced
window, ``[run.setup_done, run.setup_done + window_s)`` on the same clock.
No profiler, so a process that is slow only when nobody looks is read as it
ran.  Three kinds of number, by the parameters a metric's file gives:

    median_of   median over the window's calls of ``kind`` (``decode`` |
                ``chunk``; neither: every call) of the sum of the named
                parts, in ms: ``prep`` ``launch`` ``fetch`` ``post`` (between
                consecutive seams) and ``gap`` (the close of the call before
                to this call's opening: the scheduler and whoever drives it)
    stall_over  the time beyond ``stall_over`` times the usual, as % of the
                window: each part and the gap, grouped by (kind, batch,
                pages), the sum of ``max(0, d - stall_over x the group's
                median)`` over groups of ``min_calls`` calls or more

The log is found through ``RoundLog.recent``, the last few logs made in the
process: ``run.py`` reads per-layer metrics after the loop has returned and
dropped its engine, and holds no handle on one.  None, and the metric is
left out, where the program keeps no log (a program from before it), where
none of those logs holds a row of the window or more than one does (two
engines: whose window?), and where the ring's oldest row is younger than the
window's opening (a ring that wrapped must not read as a short window).
"""

from __future__ import annotations

import numpy as np

PARTS = ("prep", "launch", "fetch", "post")
KINDS = {"decode": "DECODE", "chunk": "CHUNK"}   # the program's names


def program_log():
    """(the program's metrics module, the logs it made last, each as its
    rows), or None for a program without the log."""
    from hetu_tpu.serve import metrics

    if not hasattr(metrics, "RoundLog"):
        return None
    return metrics, [log.rows() for log in metrics.RoundLog.recent]


def columns(fields, rows: np.ndarray) -> dict:
    """{field: column} of the rows, and each part's duration in ns under its
    name; ``gap`` is the time from the row before's close, 0 for the first
    row of the ring."""
    col = {name: rows[:, i] for i, name in enumerate(fields)}
    seams = [col["t_" + p] for p in PARTS] + [col["t_close"]]
    for part, a, b in zip(PARTS, seams, seams[1:]):
        col[part] = b - a
    col["gap"] = np.concatenate(
        [[0], col["t_prep"][1:] - col["t_close"][:-1]])
    return col


def window_of(run) -> tuple:
    """The untraced window on the log's clock, in ns."""
    t0 = run.setup_done * 1e9
    return t0, t0 + run.values["window_s"] * 1e9


def window_columns(run):
    """(:func:`columns` of the whole ring, the mask of the rows whose call
    opened in the window, the program's metrics module) of the one live log
    that holds such a row; None as the module's docstring says."""
    found = program_log()
    if found is None:
        return None
    metrics, logs = found
    t0, t1 = window_of(run)
    at = metrics.ROUND_FIELDS.index("t_prep")
    mine = [rows for rows in logs
            if ((rows[:, at] >= t0) & (rows[:, at] < t1)).any()]
    if len(mine) != 1 or mine[0][0, at] >= t0:
        return None
    col = columns(metrics.ROUND_FIELDS, mine[0])
    return col, (col["t_prep"] >= t0) & (col["t_prep"] < t1), metrics


def stall_ns(col: dict, inside: np.ndarray, over: float,
             min_calls: int) -> float:
    """ns beyond ``over`` times the usual: see the module's docstring."""
    keys = np.stack([col["kind"], col["batch"], col["pages"]], 1)[inside]
    total = 0.0
    for key in np.unique(keys, axis=0):
        group = (keys == key).all(1)
        if group.sum() < min_calls:
            continue
        for part in (*PARTS, "gap"):
            d = col[part][inside][group]
            total += np.maximum(0.0, d - over * np.median(d)).sum()
    return float(total)


def read(ctx, *, median_of=None, kind=None, stall_over=None,
         min_calls: int = 8):
    found = window_columns(ctx.run)
    if found is None:
        return None
    col, inside, metrics = found
    if stall_over is not None:
        t0, t1 = window_of(ctx.run)
        return 100.0 * stall_ns(col, inside, stall_over, min_calls) \
            / (t1 - t0)
    if kind is not None:
        inside = inside & (col["kind"] == getattr(metrics, KINDS[kind]))
    if not inside.any():
        return None
    return float(np.median(sum(col[p] for p in median_of)[inside])) / 1e6
