"""The benchmark's own library: tables, reference, trace reduction and
roofline arithmetic.  Nothing here imports the program under test."""
