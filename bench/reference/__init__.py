"""Plain references the benchmark compares the program with.

Written from the paper's and the textbooks' definitions in NumPy and
SciPy, in float64.  Nothing here imports the program under test."""
