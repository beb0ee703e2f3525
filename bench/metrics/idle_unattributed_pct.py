"""Share of device-idle time, in %, during which the host was inside none
of the program's spans `noc.args`, `noc.dispatch` and `noc.rows`: the
benchmark's own host code and whatever the spans do not cover.  With the
three `*_idle_ms` readers it splits `device_idle_pct`:
sum(*_idle_ms) x sweeps / window + this share x `device_idle_pct` =
`device_idle_pct`."""

from bench import spans


def read(ctx):
    return spans.idle_unattributed_pct(ctx)
