"""One module per model family: builds the Programs of a configuration."""
