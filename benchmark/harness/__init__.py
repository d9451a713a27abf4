"""The yardstick: traffic, payloads, the plain reference, the trace
reduction and the peaks table. Imports nothing of the program."""
