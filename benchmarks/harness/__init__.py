"""The benchmark's own yardstick: manifest loading, spans and counters,
trace reduction, peaks and operation counts, device facts. Nothing here
imports the program under test."""
