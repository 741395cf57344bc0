"""The on-chip benchmark of the FP4 serving stack.

Everything that defines the yardstick lives here: the cells and their
files, the model families (``bench/families``), the traffic generator, the
seeded weights, the plain reference that decides ``correct``, the trace
reduction and the operation/byte counts. From the program it takes only
the system under test (``ServeEngine`` and the model it serves) and the
kernel names in its compiled steps.
"""
