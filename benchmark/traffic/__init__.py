"""One driver per traffic kind; a cell's file carries its parameters."""
