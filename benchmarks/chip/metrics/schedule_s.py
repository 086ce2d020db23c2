"""Seconds of ``CompiledModel.executable``: candidate selection and the
``DeviceSchedule`` compile (``make_device_schedule``), in set-up."""


def read(run):
    return run.setup.get("schedule_s")
