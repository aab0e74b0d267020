"""The benchmark's own code: traffic, weights, the plain reference, the
trace reduction, the work counts and the peak table.  Nothing here is
imported by the program under test."""
