"""Yardstick code that no cell owns: load generation, statistics, the
reduction from a device trace to numbers, the table of peaks, the device
check and the replica wrapper."""
