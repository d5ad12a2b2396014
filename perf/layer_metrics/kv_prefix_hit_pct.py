"""Share of the window's prompt tokens that the prefix cache answered."""


def read(facts):
    counters = facts.get("counters") or {}
    submitted = facts.get("prompt_tokens_submitted")
    if not submitted or "serving.prefix.cached_tokens" not in counters:
        return None
    return 100.0 * counters["serving.prefix.cached_tokens"] / submitted
