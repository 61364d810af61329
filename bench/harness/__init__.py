"""The benchmark's own machinery: cell lookup, device checks, seeded
weights, length grids, the plain reference, trace reduction and
the result line.  The drivers, generators and metric readers that cells
name live in files of their own beside it.  Nothing here is imported by
the program under test."""
