"""One module per way of driving the program, found by a configuration's ``driver``."""
