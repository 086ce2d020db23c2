"""Device execution of PlanStore plans: schedules, runners, calibration.

The sim-to-silicon layer: ``repro.api.compile(topo).executable(root,
nbytes)`` compiles a plan into an :class:`ExecutablePlan` (static ppermute
tables + jitted runner + calibration hooks); ``calibrate`` fits
per-link-class Hockney constants from measured round times and the
resulting :class:`CalibratedCost` feeds back into the simulator
(``apply_calibration``) and ``benchmarks/roofline.py``. See docs/device.md.
"""

from repro.device.calibrate import (CalibratedCost, PredictionRow,
                                    apply_calibration, calibrate,
                                    measure_round, predict_cycle_time,
                                    prediction_report)
from repro.device.executable import (DeviceDelivery, ExecutablePlan,
                                     build_executable)
from repro.device.runner import (bbs_broadcast, binomial_broadcast,
                                 chain_broadcast, device_mesh, node_coords)
from repro.device.schedule import (DeviceSchedule, FoldedSchedule,
                                   NotDeviceExecutable, far_pairs,
                                   fold_schedule, make_device_schedule)

__all__ = [
    "CalibratedCost", "PredictionRow", "apply_calibration", "calibrate",
    "measure_round", "predict_cycle_time", "prediction_report",
    "DeviceDelivery", "ExecutablePlan", "build_executable",
    "bbs_broadcast", "binomial_broadcast", "chain_broadcast", "device_mesh",
    "far_pairs", "node_coords", "DeviceSchedule", "FoldedSchedule",
    "NotDeviceExecutable", "fold_schedule", "make_device_schedule",
]
