"""``round_host_ms.lat``'s quantity, in throughput cells."""
from harness import spec

read = spec.metric_reader("round_host_ms.lat")
