"""How a configuration maps its operator onto the chip: one plan on one
chip."""
from __future__ import annotations


def chip_spec(device):
    """The program's roofline parameters for the device it runs on; the CPU
    (tests only) is planned as a v5e."""
    from repro.utils import hw
    return hw.chip_for_device(device) if device.platform == "tpu" else hw.TPU_V5E


def compile_plan(config: dict, matrix, devices):
    """Compile the operator as the configuration places it; returns the
    plan and a dict with its format and kernel."""
    from repro.core.plan import SpMVPlan
    from repro.core.planconfig import PlanConfig

    place = config["placement"]
    if place["kind"] != "single":
        raise ValueError(f"unknown placement {place['kind']!r}")
    plan = SpMVPlan.compile(matrix, PlanConfig(format=place["format"],
                                               chip=chip_spec(devices[0])))
    return plan, {"format": plan.report.format, "kernel": plan.report.kernel}
