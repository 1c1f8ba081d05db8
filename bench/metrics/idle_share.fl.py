"""Share of the traced window in which no operation ran on the device."""


def read(trace, counts):
    return 100.0 * trace["idle_share"]
