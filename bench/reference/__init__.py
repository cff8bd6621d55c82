"""The plain references the comparison that decides ``correct`` uses.
They import nothing of the program."""
