"""Idle share of the device over the profiled part of a train cell's
window, in %: ``device_idle_share``'s reading, under a name of its own
because it moves ``tokens_per_s``."""
import pathlib
import runpy

read = runpy.run_path(str(pathlib.Path(__file__).with_name(
    "device_idle_share.py")))["read"]
