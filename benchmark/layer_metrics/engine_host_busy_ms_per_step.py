"""Milliseconds of the host's own work per engine step, timed by the
engine: each ``step()``'s wall time less its wait in
``tpu_ddp.serve.decode.fetch`` (gauge ``serve_host_busy_ms``), the
tally's ``host_busy_ms`` over its ``steps`` between the first and the
last ``tpu_ddp.serve.tally`` of the traced slice. The floor under which
a faster device program stops shortening the step."""

from benchmark.lib import tally


def read(record):
    return tally.ratio(record, "host_busy_ms", "steps")
