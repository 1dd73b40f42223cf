"""One module a model family: how the program builds it, how its
parameters map onto the reference's leaves, what its work costs."""
