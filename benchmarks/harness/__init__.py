"""The benchmark's own yardstick: manifest lookup, the timed closed
loop, the trace reduction, device peaks. Nothing here imports the
program under test."""
