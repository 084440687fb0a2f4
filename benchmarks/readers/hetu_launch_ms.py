"""Mean milliseconds of one part (``issue``, ``program``, ``runtime``,
``readback``) of the launches of ``kind`` (``serve.decode``,
``serve.prefill_chunk``, ``train.step.train``) in the traced stretch, each
launch followed from its span to its own run on the device and back by
``hetu_launches``: a program's device time is its module run's duration,
wherever the host's spans lie.  None where the trace cannot be paired."""

from benchmarks.readers import hetu_launches


def read(ctx, *, kind: str, part: str):
    records = hetu_launches.launches(ctx, kind)
    return None if records is None else hetu_launches.mean_ms(records, part)
