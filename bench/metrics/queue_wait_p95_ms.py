"""95th percentile of queue wait (submit to first dispatch of the request's
batch) over requests finished in the window, from the program's
ServingStats."""


def read(rec):
    s = rec["stats"]
    return s.queue_wait_p95_s * 1e3 if s is not None and s.completed else None
