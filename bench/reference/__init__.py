"""The plain reference of the fabric: a scalar, tick-by-tick model of
the semantics the benchmark's cells run (static ECMP routes, DCQCN
senders, switch ports with ECN and per-class PFC, and the paper's
receiving host with its Jet cache pool or DDIO).

One Python object per port, sender and receiver, advanced in float64.
Nothing here imports the program, so a change to the program cannot
move the yardstick.  The benchmark builds the reference's scenarios from
the same seeded parameters as the program's, with these classes.
"""
