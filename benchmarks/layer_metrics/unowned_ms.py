"""Device milliseconds a step in instructions no scope of the program
owns, neither by their own name nor through their users or producers,
first device."""

from benchmarks.lib import owners


def compute(observed):
    return owners.ms(observed, owner=r"unowned$")
