"""The benchmark's own machinery: loading cells by name, the traffic
generator, the closed and open loops, the trace reduction, the peaks
table, the roofline byte counts and the plain reference.

Nothing here is part of the system under test; the program is imported
only for the session API the loops drive and its compile-cache set-up.
"""
