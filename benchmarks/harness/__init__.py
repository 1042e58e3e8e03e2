"""The benchmark's own code: nothing here is imported by the program."""
