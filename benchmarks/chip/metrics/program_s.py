"""Seconds to program the chip (write noise, drift to the deployment age,
GDC), on the host clock, ended by ``block_until_ready``."""


def read(r):
    return r.counters.get("program_s")
