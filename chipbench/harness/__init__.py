"""The chip benchmark's yardstick: spec loading, traffic, the closed loop,
trace reduction, FLOP and byte counts, the plain reference and the
output check. Nothing here is imported by the program under test."""
