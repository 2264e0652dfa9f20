"""Standstills inside the traced window, from the run's own profile: the
milliseconds by which the process's watcher woke late
(``ray_tpu/observability/health.py`` ``FreezeWatcher``), summed over the
events of one cause. A wake 20 ms to 1 s late is an instant
``stall::late_wake`` (``late_ms``, ``cpu_ms``, ``cause``), one more than
a second late the kept ``stall::host_freeze`` (``late_s``, ``cpu_s``,
``cause``); under a profile both land in ``/host:CPU`` on the watcher's
thread's line, on the device planes' axis (``host_plane.py``), as they
END: the standstill is the ``late_ms`` before the event. Counted are the
events that start inside the device's traced stretch.

``{"reader": "late_wakes", "cause": "host"}``: the machine's (the
process got no CPU); ``"process"``: the program's own (a native call
held the interpreter's lock). A device idle gap of that window and
every share over ``window_s`` on the same line are off by this much.

0 where the window held none; nothing where the profile has no host
plane. A program older than the instant reads 0 and says nothing by it.
"""

from benchmark import host_plane

LATE_WAKE, FREEZE = "stall::late_wake", "stall::host_freeze"
# event -> (the attribute that holds its lateness, milliseconds a unit)
LATENESS = {LATE_WAKE: ("late_ms", 1.0), FREEZE: ("late_s", 1e3)}


def read(spec: dict, obs: dict):
    return standstill_ms(host_plane.of_run(), spec["cause"])


def standstill_ms(planes, cause: str):
    if host_plane.spans(planes, LATE_WAKE) is None:
        return None
    return sum((scale * stats.get(key, 0.0)
                for name, (key, scale) in LATENESS.items()
                for _, _, _, stats in host_plane.spans(planes, name)
                if stats.get("cause") == cause), 0.0)
