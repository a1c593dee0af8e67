"""Plain references of the configurations: no import from the program."""
