"""Seconds in ``InstanceOptimizer.run_calibration`` during set-up (host
clock around the call; calibration returns host statistics)."""


def read(ctx):
    return ctx.timers.get("calibrate_s")
