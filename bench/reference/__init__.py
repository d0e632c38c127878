"""Plain references the benchmark compares served answers with.  They
import nothing of the program under test: a label-string grammar in CNF
and an edge list are all they take."""
