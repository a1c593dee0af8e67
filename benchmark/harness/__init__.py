"""The benchmark's own yardstick: nothing here imports the program."""
