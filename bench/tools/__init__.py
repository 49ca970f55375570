"""Tools of the chip benchmark that a check never runs: the knee sweep and
the readings that set the limits of the comparison."""
