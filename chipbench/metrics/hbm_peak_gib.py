"""hbm_peak_gib (GiB): the fullest device's peak after the window (the
process's peak: set-up included), ``peak_bytes_in_use`` plus
``peak_bytes_reserved``. The TPU runtime keeps a program's temporaries in
reserved memory, which ``peak_bytes_in_use`` does not count; reserved
memory is held once a program has run, so the sum is the most the
process held at once when the in-use peak came after the largest
program's first run, and an upper bound otherwise."""


def read(run):
    return run.peak_bytes / 2**30 if run.peak_bytes is not None else None
