"""Mean real (unpadded) requests per executed batch over requests finished
in the window, from the program's ServingStats."""


def read(rec):
    s = rec["stats"]
    return s.mean_batch_real if s is not None and s.completed else None
