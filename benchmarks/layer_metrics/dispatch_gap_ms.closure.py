"""Mean device-idle gap between consecutive programs (harness/readers.gap_ms)."""

from harness import readers


def read(ctx):
    return readers.gap_ms(ctx)
