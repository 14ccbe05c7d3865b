// estnative: host-runtime native layer for elasticsearch_tpu.
//
// Reference analog: the reference ships native code where the JVM was too
// slow or couldn't reach the OS (lib/sigar JNI for OS metrics,
// common/jna for mlockall). Here the native layer covers the HOST hot
// paths that feed the TPU — the device compute itself is XLA/Pallas:
//
//   * tokenize_batch: standard-analyzer tokenization (word split +
//     lowercase + stopword removal) over a batch of documents. This is
//     the indexing-path hot loop (ref: Lucene StandardTokenizer inside
//     index/analysis/); regex tokenization in Python is ~10-30x slower.
//   * wal_*: append-only write-ahead log records with CRC32C-style
//     checksums and explicit fsync control (ref: index/translog/fs/
//     FsTranslog.java buffered variant).
//
// Pure C ABI (extern "C") consumed via ctypes — no pybind11 dependency.
// Build: g++ -O3 -shared -fPIC (see ../build.py).

#include <cerrno>
#include <cstdint>
#include <cstdio>
#include <cstring>
#include <cstdlib>
#include <string>
#include <unordered_set>
#include <vector>

#include <fcntl.h>
#include <unistd.h>

extern "C" {

// ---------------------------------------------------------------------------
// crc32 (IEEE, zlib-compatible) — table-based
// ---------------------------------------------------------------------------

static uint32_t crc_table[256];
static bool crc_init_done = false;

static void crc_init() {
    for (uint32_t i = 0; i < 256; i++) {
        uint32_t c = i;
        for (int k = 0; k < 8; k++)
            c = (c & 1) ? 0xEDB88320u ^ (c >> 1) : c >> 1;
        crc_table[i] = c;
    }
    crc_init_done = true;
}

uint32_t est_crc32(const uint8_t* buf, int64_t len) {
    if (!crc_init_done) crc_init();
    uint32_t c = 0xFFFFFFFFu;
    for (int64_t i = 0; i < len; i++)
        c = crc_table[(c ^ buf[i]) & 0xFF] ^ (c >> 8);
    return c ^ 0xFFFFFFFFu;
}

// ---------------------------------------------------------------------------
// tokenizer
// ---------------------------------------------------------------------------

// Word characters: ASCII alnum, underscore, apostrophes inside words;
// any byte >= 0x80 (UTF-8 multibyte sequences group into one token, the
// same grouping the Python \w regex produces for contiguous non-Latin
// words). The Python layer routes text through here and keeps exact
// regex parity for ASCII inputs.
static inline bool is_word_byte(uint8_t c) {
    return (c >= '0' && c <= '9') || (c >= 'a' && c <= 'z') ||
           (c >= 'A' && c <= 'Z') || c == '_' || c >= 0x80;
}

struct Stopset {
    std::unordered_set<std::string> words;
};

// stopwords: '\n'-separated utf-8; returns opaque handle
void* est_stopset_new(const char* words, int64_t len) {
    Stopset* s = new Stopset();
    const char* p = words;
    const char* end = words + len;
    while (p < end) {
        const char* nl = (const char*)memchr(p, '\n', end - p);
        if (!nl) nl = end;
        if (nl > p) s->words.emplace(p, nl - p);
        p = nl + 1;
    }
    return s;
}

void est_stopset_free(void* h) { delete (Stopset*)h; }

// Tokenize n_docs documents (concatenated utf-8 in `buf`, doc i spans
// [offsets[i], offsets[i+1])). Output: tokens '\0'-separated in out_buf,
// out_counts[i] = number of tokens of doc i. Returns bytes written to
// out_buf, or -(needed) if out_cap is too small.
int64_t est_tokenize_batch(const uint8_t* buf, const int64_t* offsets,
                           int64_t n_docs, int lowercase, void* stopset,
                           uint8_t* out_buf, int64_t out_cap,
                           int32_t* out_counts) {
    Stopset* stops = (Stopset*)stopset;
    int64_t w = 0;
    std::string tok;
    bool overflow = false;
    for (int64_t d = 0; d < n_docs; d++) {
        int32_t count = 0;
        const uint8_t* p = buf + offsets[d];
        const uint8_t* end = buf + offsets[d + 1];
        while (p < end) {
            while (p < end && !is_word_byte(*p)) p++;
            if (p >= end) break;
            const uint8_t* start = p;
            while (p < end &&
                   (is_word_byte(*p) ||
                    // apostrophe stays inside a word (don't, o'brien)
                    ((*p == '\'' || *p == 0xE2 /* ' utf8 lead */) &&
                     p + 1 < end && is_word_byte(p[1]) && p > start))) {
                if (*p == 0xE2) {
                    // only consume a right-single-quote sequence E2 80 99
                    if (p + 2 < end && p[1] == 0x80 && p[2] == 0x99 &&
                        p + 3 < end && is_word_byte(p[3])) {
                        p += 3;
                        continue;
                    }
                    break;
                }
                p++;
            }
            int64_t n = p - start;
            tok.assign((const char*)start, n);
            if (lowercase) {
                for (char& c : tok)
                    if (c >= 'A' && c <= 'Z') c += 32;
            }
            if (stops && stops->words.count(tok)) continue;
            int64_t need = (int64_t)tok.size() + 1;
            if (w + need > out_cap) { overflow = true; w += need; continue; }
            memcpy(out_buf + w, tok.data(), tok.size());
            out_buf[w + tok.size()] = 0;
            w += need;
            count++;
        }
        out_counts[d] = count;
    }
    return overflow ? -w : w;
}

// ---------------------------------------------------------------------------
// WAL (write-ahead log)
// ---------------------------------------------------------------------------

struct Wal {
    int fd;
    int64_t size;
};

void* est_wal_open(const char* path) {
    int fd = ::open(path, O_WRONLY | O_CREAT | O_APPEND, 0644);
    if (fd < 0) return nullptr;
    Wal* w = new Wal();
    w->fd = fd;
    w->size = ::lseek(fd, 0, SEEK_END);
    return w;
}

// `len` bytes of whole records, each [u32 len][u32 crc32(payload)]
// [payload], framed by the caller: a batch goes down in one write(),
// which O_APPEND keeps contiguous. Returns new size or -1
int64_t est_wal_write(void* h, const uint8_t* records, int64_t len) {
    Wal* w = (Wal*)h;
    int64_t done = 0;
    while (done < len) {
        ssize_t n = ::write(w->fd, records + done, len - done);
        if (n < 0) {
            if (errno == EINTR) continue;
            return -1;
        }
        done += n;
        w->size += n;
    }
    return w->size;
}

int est_wal_sync(void* h) { return ::fdatasync(((Wal*)h)->fd); }

int64_t est_wal_size(void* h) { return ((Wal*)h)->size; }

void est_wal_close(void* h) {
    Wal* w = (Wal*)h;
    ::close(w->fd);
    delete w;
}

}  // extern "C"
