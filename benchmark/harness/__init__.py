"""The general parts of the harness: nothing here knows a cell's name."""
