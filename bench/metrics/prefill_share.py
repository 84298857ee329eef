"""Share of the device's busy time spent in the engine's prefill
programs (every program whose name holds ``prefill``), in %, from the
trace."""


def read(ctx):
    tr = ctx.trace
    if tr is None or not tr.busy_s:
        return None
    pre = sum(s for name, s in tr.program_s.items() if "prefill" in name)
    return 100.0 * pre / (tr.busy_s * tr.devices)
