"""Block manager (``emem_vm/block_manager.py``): prompt tokens the engine
counted as shared from pages already in the pool
(``shared_prompt_tokens``), over the prompt tokens admitted in the
window."""


def read(run):
    spans = [s for s in run.spans if s.name == "admit"]
    prompt = sum(s.prompt for s in spans)
    if not prompt:
        return None
    return sum(s.shared for s in spans) / prompt
