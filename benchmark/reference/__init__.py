"""Plain references that import nothing of the program: they read the plain
values of a generated state (ints and bytes) and compute the answer again
from the consensus specification, with hashlib and numpy alone."""
