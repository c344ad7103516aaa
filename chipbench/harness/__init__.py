"""The chip benchmark's yardstick: spec loading, traffic, the closed loop,
trace reduction, the peaks and the roofline, the SplitQuant replica every
reference shares, and the output check. Each architecture's plain
reference and FLOP and byte counts are its own module under
``architectures/``. Nothing here is imported by the program under
test."""
