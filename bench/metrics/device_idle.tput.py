"""``device_idle.lat``'s quantity, in throughput cells."""
from harness import spec

read = spec.metric_reader("device_idle.lat")
