"""Part ``driver``: today's driver, counting completions. Each completed
request that succeeded carries the count so far and the ``steady_jobs`` the
driver was handed in its ``note`` (which run.py reads of failed requests
only)."""

from benchmark import driver as default

STAMP = "stub-driver"


def read_stamp(note: str):
    """``(completions so far, steady_jobs)`` of a stamped note, else
    ``None``."""
    words = note.split()
    if len(words) != 3 or words[0] != STAMP:
        return None
    return int(words[1]), int(words[2])


class Driver(default.Driver):
    completions = 0

    def collect(self) -> list:
        done = super().collect()
        for req in done:
            self.completions += 1
            if req.ok:
                req.note = f"{STAMP} {self.completions} {self.steady_jobs}"
        return done
