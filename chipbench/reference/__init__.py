"""Plain references the benchmark's check compares the program with. They
import nothing of the program and take none of its weights."""
