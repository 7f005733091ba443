"""Plain float32 references, one module per net (`<net>.py`: `init`,
`apply`), importing nothing of the program."""
