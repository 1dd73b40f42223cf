"""Plain references, one a family. They import nothing of the program."""
