"""The benchmark's own library: everything the yardstick is made of lives
under benchmark/ (traffic generation, client, statistics, trace reduction,
ops-and-bytes functions, peaks, reference, the correctness comparison)."""
