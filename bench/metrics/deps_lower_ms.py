"""Host milliseconds per traced question in the program's dependency
lowering (``make_jobset``'s span ``repro.deps.lower``), from the entry's
``deps_lower_us`` counter: the question's move of the program counter
``deps.lower_us``.  Nothing where the program has no such counter."""


def read(view):
    lowered = view.traced.get("deps_lower_us")
    questions = view.traced.get("questions")
    if lowered is None or not questions:
        return None
    return lowered / 1e3 / questions
